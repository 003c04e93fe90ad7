"""``ops/attn_pre.py``'s pass between a softmax attention's projections and
its flash core (``attn_pre_fwd`` / ``attn_pre_bwd``), interpreted on the CPU
a grid step at a time, in token tiles of 16: against
``models/layers.py:attn_inputs``, the ``jnp`` form every other layer and
mode runs (the oracle), values and EVERY gradient, over norm on / off x
rotation on / off, grouped 32 / 4 and equal heads, heads of 128 and 256,
positions [S] and [B, S], a sequence that is no whole number of tiles; the
rule's truth table and the modes that keep the ``jnp`` form; a
``TransformerLM`` of Trinity-Mini's and of Keye-VL-2.0's layer form at
heads of 128 with the pass against the same model without it. (Mosaic takes
the kernels at the cells' sizes in ``tests/test_olmoe.py``, beside the
flash kernels'.)

Tolerances, of the largest entry: float32 values are the oracle's to the
bit or a rounding (the same equations; XLA:CPU may contract a multiply-add
on one side), float32 gradients differ by the order of sums (``RTOL``, the
model tests'); bfloat16 values are the oracle's to a rounding (the same
rounding points), bfloat16 gradients differ by the roundings the oracle's
backward makes in between and the kernel does not (5e-2,
``tests/test_mamba_mixer.py``'s).
"""
import dataclasses
import functools
import itertools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import layers, lm
from autodist_tpu.ops import attn_pre
from autodist_tpu.ops import flash_attention as fa
from autodist_tpu.telemetry import scopes
from autodist_tpu.telemetry import spans as tel
from tests.test_afmoe import tiny_config as trinity_tiny
from tests.test_flash_attention import kernel_calls
from tests.test_keye_vl2 import tiny_config as keye_tiny
from tests.test_kimi_linear import close, flat
from tests.test_mamba_mixer import kernel_scopes

TILE, SEQ, BATCH = 16, 40, 2     # two tiles and 8 rows of a third
EPS, THETA = 1e-5, 1e4
BF16_RTOL = 5e-2
HEADS = {"grouped_32_4": (32, 4), "equal_4": (4, 4)}
LEAVES = ("q", "k", "v", "q_norm", "k_norm")
# every (norm, rotation) at 32 / 4 heads of 128 and at 4 / 4 heads of 256,
# positions [S] on the first and a row a batch entry on the second where the
# layer rotates; the norm with the rotation at the two other pairings too
# (each case compiles both renderings: the suite's time, not coverage, keeps
# this short of the full product)
CASES = [dict(normed=n, rotated=r, heads=h, head_dim=d,
              positions=p if r else "S")
         for n, r in itertools.product((True, False), (True, False))
         for h, d, p in (("grouped_32_4", 128, "S"), ("equal_4", 256, "BS"))
         ] + [dict(normed=True, rotated=True, heads=h, head_dim=d,
                   positions=p)
              for h, d, p in (("grouped_32_4", 256, "BS"),
                              ("equal_4", 128, "S"))]


def case_id(case):
    return "-".join(("norm" if case["normed"] else "no_norm",
                     "rope" if case["rotated"] else "nope", case["heads"],
                     "d%d" % case["head_dim"], "pos_" + case["positions"]))


def operands(heads, head_dim, dtype, positions, seq=SEQ):
    """(the projections' outputs q, k, v, the two norm weights, the
    positions: [S], or a row of its own a batch entry)."""
    r = np.random.RandomState(0)
    f = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)  # noqa: E731
    H, Hkv = HEADS[heads]
    pos = jnp.arange(seq) if positions == "S" else jnp.stack(
        [jnp.arange(seq) + 7 * b for b in range(BATCH)])
    return ((f(BATCH, seq, H, head_dim).astype(dtype),
             f(BATCH, seq, Hkv, head_dim).astype(dtype),
             f(BATCH, seq, Hkv, head_dim).astype(dtype),
             1.0 + 0.1 * f(head_dim), 1.0 + 0.1 * f(head_dim)), pos)


def oracle(dtype, normed, rotated, pos, q, k, v, wq, wk):
    """``attn_inputs`` on ``nn.RMSNorm``s of the two weights, as
    ``MultiHeadAttention`` calls it, in the kernels' layout."""
    norms = [tuple(functools.partial(
        nn.RMSNorm(epsilon=EPS, dtype=dtype).apply, {"params": {"scale": w}})
        for w in (wq, wk))] if normed else []
    q, k = layers.attn_inputs(q, k, norms, pos, THETA if rotated else None)
    return tuple(t.transpose(0, 2, 1, 3) for t in (q, k, v))


def fused(dtype, normed, rotated, pos, q, k, v, wq, wk):
    """(v is the caller's to transpose, as ``MultiHeadAttention`` does.)"""
    del dtype
    return attn_pre.attn_pre(
        q, k, (wq, wk) if normed else None, EPS, pos,
        layers.rope_inv_freq(q.shape[-1], THETA) if rotated else None,
        TILE) + (v.transpose(0, 2, 1, 3),)


@functools.lru_cache(maxsize=None)
def both(dtype_name, **case):
    """{rendering: (q, k, v heads-first, the gradients of a weighted sum of
    them by ``LEAVES``)}, computed once a case."""
    dtype = jnp.dtype(dtype_name)
    args, pos = operands(case["heads"], case["head_dim"], dtype,
                         case["positions"])
    r = np.random.RandomState(1)     # the same cotangents for both
    weights = [jnp.asarray(r.randn(*t.transpose(0, 2, 1, 3).shape),
                           jnp.float32) for t in args[:3]]
    out = {}
    for form in (oracle, fused):
        form = functools.partial(form, dtype, case["normed"],
                                 case["rotated"], pos)

        def loss(*t):
            got = form(*t)
            return sum(jnp.sum(g.astype(jnp.float32) * w)
                       for g, w in zip(got, weights)), got

        (_, got), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(5)), has_aux=True))(*args)
        out[form.func.__name__] = (got, grads)
    return out


# --------------------------------------------- values and every gradient


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_attn_pre_writes_attn_inputs_values_heads_first(case):
    """q, k and v in [B, H, S, D], float32: the norm's float32 statistics
    and ``x * (rsqrt * w)``, the rotation by the tables, a tile whose last
    rows lie past the sequence's end."""
    (got, _), (want, _) = (both("float32", **case)[form]
                           for form in ("fused", "oracle"))
    H, Hkv = HEADS[case["heads"]]
    for g, w, n in zip(got, want, (H, Hkv, Hkv)):
        assert g.shape == w.shape == (BATCH, n, SEQ, case["head_dim"])
        assert g.dtype == w.dtype == jnp.float32
        close(g, w, 1e-6)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_attn_pre_bwd_is_autodiff_of_attn_inputs(case):
    """The raw q, k and v (the rotation's transpose, the norm's backward
    from the statistics made again; v is a transpose) and the two norm
    weights (a partial sum a grid step over the tile's LIVE rows,
    added up outside), float32."""
    (_, got), (_, want) = (both("float32", **case)[form]
                           for form in ("fused", "oracle"))
    for leaf, g, w in zip(LEAVES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, leaf
        if leaf.endswith("_norm") and not case["normed"]:
            np.testing.assert_array_equal(np.asarray(g), 0.0)
            continue
        assert np.abs(np.asarray(w)).max() > 0, leaf
        close(g, w)


BF16_CASES = [c for c in CASES if c["normed"] and c["rotated"]][:2]


@pytest.mark.parametrize("case", BF16_CASES, ids=case_id)
def test_attn_pre_in_bfloat16_rounds_where_attn_inputs_rounds(case):
    """The model's dtype: the normed value rounded before the rotation
    widens it again, the rotation rounded once: the oracle's values to a
    rounding; the gradients to the roundings the oracle's backward makes in
    between (the kernel's is float32 to its output)."""
    (got, grads), (want, want_grads) = (
        both("bfloat16", **case)[form] for form in ("fused", "oracle"))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.bfloat16
        close(g.astype(jnp.float32), w.astype(jnp.float32), 2.0 ** -7)
    for leaf, g, w in zip(LEAVES, grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype, leaf
        close(g.astype(jnp.float32), w.astype(jnp.float32), BF16_RTOL)


def test_rows_past_the_sequences_end_reach_no_weight_gradient():
    """40 tokens in tiles of 16 and in ONE tile of 48: the norm weights'
    gradients are the same sums (the 8 rows past the end hold whatever the
    block read there)."""
    args, pos = operands("grouped_32_4", 128, jnp.float32, "S")

    def grads(tile):
        return jax.jit(jax.grad(lambda *t: sum(
            jnp.sum(jnp.sin(o)) for o in attn_pre.attn_pre(
                *t[:2], t[3:], EPS, pos, layers.rope_inv_freq(128, THETA),
                tile)), argnums=(3, 4)))(*args)
    for g, w in zip(grads(TILE), grads(48)):
        assert np.all(np.isfinite(np.asarray(g)))
        close(g, w)


# ------------------------------------------------------------- the rule

FLASH = fa.make_flash_attn_fn(causal=True)


@pytest.mark.parametrize("fields, fused_pre", [
    (dict(), True),                                  # Trinity's window layer
    (dict(rotated=False), True),                     # its global NoPE layer
    (dict(head_norm=False), True),                   # SmallThinker's, Ouro's
    (dict(head_dim=256), True),
    (dict(head_norm=False, rotated=False), False),   # nothing to fuse
    (dict(head_dim=64), False),                      # LFM2's heads
    (dict(head_dim=192), False),                     # no whole lane tiles
    (dict(attn_fn=None), False),                     # XLA's core
    (dict(attn_fn=lambda q, k, v, mask: q), False),  # a reference attn_fn
    (dict(seq=1004), False),                         # no tile of 8 rows
    (dict(full_width_norm=True), False),             # OLMoE's norm
    (dict(full_width_norm=True, head_norm=False), False)],
    ids=lambda t: "-".join("%s=%s" % (k, getattr(v, "__name__", v))
                           for k, v in t.items()) if isinstance(t, dict)
    else str(t))
def test_the_layers_fields_and_shapes_pick_the_pass(fields, fused_pre):
    """No argument, flag or model name: the flash adapter (which says of
    itself that it takes operands heads-first), a sequence it tiles, heads
    of whole 128-lane tiles, a per-head norm or a rotation, no norm over
    all features."""
    asked = dict(attn_fn=FLASH, seq=16384, head_dim=128, head_norm=True,
                 rotated=True, full_width_norm=False)
    asked.update(fields)
    assert attn_pre.runs_fused(**asked) is fused_pre


def attention(**kw):
    fields = dict(num_heads=4, head_dim=128, attn_fn=FLASH, use_bias=False,
                  rope_theta=THETA, head_norm_eps=EPS)
    fields.update(kw)
    return layers.MultiHeadAttention(**fields)


def passes_traced(layer, *args, method=None, **kw):
    x = jnp.zeros((1, 32, 24), jnp.float32)
    params = jax.eval_shape(functools.partial(
        layer.init, positions=jnp.arange(32)), jax.random.PRNGKey(0), x)
    jaxpr = jax.make_jaxpr(lambda p: (method or layer.apply)(
        p, x, *args, positions=jnp.arange(32), **kw))(params)
    return kernel_calls(jaxpr.jaxpr, "attn_pre_fwd")


def test_training_mode_traces_two_passes_and_every_other_mode_none():
    """q's and k's; prefill (``return_kv``), cached decode, a masked
    call and initialisation keep the ``jnp`` form, and so does a layer the
    rule does not name (heads of 64; a norm over all features)."""
    assert passes_traced(attention()) == 2
    assert passes_traced(attention(), return_kv=True) == 0
    assert passes_traced(attention(), mask=jnp.ones((1, 32), bool)) == 0
    cache = tuple(jnp.zeros((1, 32, 4, 128)) for _ in range(2))
    x1 = jnp.zeros((1, 1, 24))
    layer = attention(attn_fn=None)
    params = jax.eval_shape(functools.partial(
        layer.init, positions=jnp.arange(32)), jax.random.PRNGKey(0),
        jnp.zeros((1, 32, 24)))
    for decoder in (layer, attention()):
        jaxpr = jax.make_jaxpr(lambda p: decoder.apply(
            p, x1, cache=cache, cursor=jnp.zeros((1,), jnp.int32),
            positions=jnp.zeros((1,), jnp.int32)))(params)
        assert kernel_calls(jaxpr.jaxpr, "attn_pre_fwd") == 0
    init = jax.make_jaxpr(functools.partial(
        attention().init, positions=jnp.arange(32)))(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 24)))
    assert kernel_calls(init.jaxpr, "attn_pre_fwd") == 0
    assert passes_traced(attention(head_dim=64)) == 0
    assert passes_traced(attention(head_norm_eps=None, qk_norm_eps=EPS)) == 0


def test_the_pass_reads_the_norms_own_parameters():
    """The tree ``make_norm`` initialised (``q_norm/scale``,
    ``k_norm/scale``) is the tree the fused layer applies."""
    layer = attention(num_kv_heads=2)
    x = jnp.zeros((1, 32, 24), jnp.float32)
    params = jax.jit(functools.partial(layer.init, positions=jnp.arange(32)))(
        jax.random.PRNGKey(0), x)
    assert sorted(params["params"]) == ["k_norm", "key", "out", "q_norm",
                                        "query", "value"]
    out = jax.jit(functools.partial(layer.apply, positions=jnp.arange(32)))(
        params, x)
    assert out.shape == x.shape


# ------------------------------------------- the model, fused and unfused

ROWS = 16


def trinity_form():
    """W G W of Trinity-Mini's five layers at heads of 128: a window layer
    that norms and rotates, the global layer that norms alone, gates on."""
    return trinity_tiny(num_layers=3, layout=(1, 0, 1), num_heads=8,
                        num_kv_heads=1, head_dim=128), 48


def keye_form():
    """Keye-VL-2.0's layer at heads of 128: a chosen set of keys, grouped
    heads, a per-head norm, a rotation."""
    return keye_tiny(num_layers=1, head_dim=128), 32


@pytest.fixture(scope="module", params=[trinity_form, keye_form],
                ids=["trinity_mini", "keye_vl2"])
def models(request):
    """{rendering: (loss, gradients by leaf, the differentiated loss's
    jaxpr, the pass counter's rise, the gauge)} of one model on the flash
    kernels (16-row tiles), with ``attn_pre`` and with the rule answering
    no, float32: ONE trace a rendering, compiled and read."""
    cfg, seq = request.param()
    rows, fa._ROWS = fa._ROWS, ROWS
    rule = attn_pre.runs_fused
    try:
        out = {}
        for form in ("fused", "unfused"):
            if form == "unfused":
                attn_pre.runs_fused = lambda *a, **kw: False
            loss_fn, params, _, _ = lm.make_train_setup(
                cfg, seq_len=seq, batch_size=2, seed=0, attention="flash")
            batch = {"tokens": np.random.RandomState(1).randint(
                0, 256, (2, seq + 1)).astype(np.int32)}
            before = tel.counters().get("attention.pre_passes", 0)
            with jax.default_matmul_precision("highest"):
                step = jax.jit(jax.value_and_grad(loss_fn)).trace(params,
                                                                  batch)
                loss, grads = step.lower().compile()(params, batch)
            out[form] = (loss, flat(grads), step.jaxpr,
                         tel.counters().get("attention.pre_passes", 0)
                         - before, tel.gauges()["attention.fused_pre_layers"])
        return cfg, out
    finally:
        fa._ROWS, attn_pre.runs_fused = rows, rule


def test_the_fused_models_loss_and_every_gradient_leaf_are_the_unfused(
        models):
    _, out = models
    (loss, grads, *_), (want, want_grads, *_) = out["fused"], out["unfused"]
    close(loss, want)
    assert sorted(grads) == sorted(want_grads)
    for name, g in grads.items():
        assert np.abs(np.asarray(want_grads[name])).max() > 0 or (
            "indexer" in name or "bias" in name), name
        close(g, want_grads[name])


def test_every_attention_layer_holds_two_passes_a_direction(models):
    """... and the gauge says how many layers, the counter how many
    launches were traced (the differentiated loss's one trace: q's and k's
    forward pass and backward pass a layer); without the rule none."""
    cfg, out = models
    _, _, jaxpr, passes, gauge = out["fused"]
    n = cfg.num_layers
    assert kernel_calls(jaxpr.jaxpr, "attn_pre_fwd") == 2 * n
    assert kernel_calls(jaxpr.jaxpr, "attn_pre_bwd") == 2 * n
    assert kernel_calls(jaxpr.jaxpr, "flash_fwd") == n
    assert gauge == n and passes > 0 and passes % (2 * n) == 0
    _, _, jaxpr, passes, gauge = out["unfused"]
    assert kernel_calls(jaxpr.jaxpr, "attn_pre_fwd") == 0
    assert kernel_calls(jaxpr.jaxpr, "flash_fwd") == n
    assert (gauge, passes) == (0, 0)


def test_the_passes_sit_under_attention_and_outside_the_cores_scope(models):
    """``attn_ms_per_step`` holds the four passes and
    ``attn_core_ms_per_step`` the flash kernels alone, forward and
    backward rules alike."""
    _, out = models
    found = list(kernel_scopes(out["fused"][2].jaxpr))
    assert {name for name, _ in found} == {
        "attn_pre_fwd", "attn_pre_bwd", "flash_fwd", "flash_bwd"}
    for name, stack in found:
        inside = [part.rsplit("(", 1)[-1].rstrip(")")
                  for part in stack.split("/")]
        assert scopes.ATTENTION in inside, (name, stack)
        assert (scopes.ATTN_CORE in inside) == name.startswith("flash_"), (
            name, stack)
        assert ("transpose(" in stack) == name.endswith("_bwd"), (name, stack)


def test_a_recomputed_block_keeps_the_passes_q_and_makes_k_again():
    """``checkpoint_name(qt, KEPT)`` is on the pass's own output: under
    the block's policy the recomputed forward holds no pass for q (dead:
    q is kept, and its backward reads the RAW projection) and k's
    again."""
    cfg, seq = trinity_form()
    cfg = dataclasses.replace(cfg, num_layers=1, window_layers=(1,),
                              rope_layers=(1,))
    model = lm.TransformerLM(cfg, attn_fn=FLASH, remat_blocks=True)
    ids = jnp.zeros((1, seq), jnp.int32)
    params = jax.eval_shape(lm.TransformerLM(cfg).init,
                            jax.random.PRNGKey(0), ids)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(model.apply(
        p, ids, method=lm.TransformerLM.hidden))))(params).jaxpr
    assert kernel_calls(jaxpr, "attn_pre_fwd") == 2 + 1
    assert kernel_calls(jaxpr, "attn_pre_bwd") == 2
    assert kernel_calls(jaxpr, "flash_fwd") == 1
