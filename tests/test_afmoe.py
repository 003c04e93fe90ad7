"""Trinity-Mini (``afmoe``) on the normal training path
(``LMConfig.trinity_mini_26b_a3b``): GATED softmax attention over grouped
K/V heads with a per-head QK-norm, sliding-window rotary and global NoPE
layers under four norms a block, a leading dense layer, a share of
sigmoid-routed experts beside a shared one, against the plain float32
reference ``benchmark/reference/afmoe.py`` at a tiny size, the sequence
LONGER than the tiny window. ``tests/test_afmoe_cell.py`` has the
configuration file, the closed forms and the cell's record.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums (a fused rsqrt against a divide by sqrt, one
einsum over all held experts against one expert after another, K/V heads
repeated against indexed, the kernels' online softmax a tile at a time
against one softmax a row). ``RTOL`` 1e-5 of the largest entry holds
logits, loss and EVERY gradient leaf of the five-layer model: a gate left
out or fed the un-normed stream, a window ignored, a rotation on the wrong
layer, a norm or the router's scale left out or a bfloat16 matmul misses by
orders of magnitude, and each is planted below and seen.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models import layers, lm
from autodist_tpu.ops import flash_attention as fa
from autodist_tpu.parallel import expert
from autodist_tpu.telemetry import scopes
from autodist_tpu.telemetry import spans as tel
from benchmark.reference import afmoe as ref
from benchmark.tools import loss_limit_afmoe as tool
from tests.test_kimi_linear import close, flat, routed_layer
from tests.test_smallthinker import xla_windowed

TOP_K = 3
HELD = (0, 1, 2, 3)
SEQ = 48
WINDOW = 10      # shorter than SEQ, and no multiple of the 16-row test tile
ROWS = 16        # the kernels' tile in the tests below: SEQ spans three
LAYOUT = (1, 1, 0, 1, 1)    # published layers 1-5: W W G W W


def tiny_config(**kw):
    """The cell's five layers (published 1-5: a dense window layer, then
    routed W G W W) at d 64: 16 query heads over 2 K/V heads of 8 (groups
    of EIGHT, as published), a dense width of 96, 16 experts of width 24 of
    which 4 are held beside a shared one, top-3 renormalised x 2.826, a
    window of 10, theta 1e4, an untied table of 256 rows."""
    sizes = dict(vocab_size=256, d_model=64, num_heads=16, num_kv_heads=2,
                 head_dim=8, mlp_dim=24, dense_dim=96,
                 first_k_dense_replace=1, num_experts=16,
                 experts_per_token=TOP_K, experts_held=HELD,
                 sliding_window=WINDOW)
    sizes.update(kw)
    n = sizes.pop("num_layers", 5)
    layout = sizes.pop("layout", LAYOUT[:n])
    return dataclasses.replace(
        lm.LMConfig.trinity_mini_26b_a3b(
            num_layers=n, window_layers=layout, rope_layers=layout,
            max_seq_len=64), **sizes)


def batches(n, rows=2, vocab=256, seed=1, seq=SEQ):
    rng = np.random.RandomState(seed)
    return [{"tokens": rng.randint(0, vocab, (rows, seq + 1)).astype(np.int32)}
            for _ in range(n)]


def reference_loss(params, batch, held=HELD):
    return ref.nll_sum(params, batch, TOP_K, held, WINDOW) / ref.batch_weight(
        {"tokens": np.zeros(batch["tokens"].shape)})


def reference_logits(params, ids):
    return ref.logits_fn(params, ids, TOP_K, HELD, WINDOW)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    loss_fn, params, _, apply_fn = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0)
    return cfg, loss_fn, params, apply_fn, batches(1)[0]


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    _, loss_fn, params, _, batch = tiny
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    return got[0], want[0], flat(got[1]), flat(want[1])


@pytest.fixture
def small_tiles(monkeypatch):
    """The kernels at 16-row tiles: SEQ spans three of them."""
    monkeypatch.setattr(fa, "_ROWS", ROWS)


ATTN_LEAVES = (
    ["MultiHeadAttention_0/%s/kernel" % n
     for n in ("query", "key", "value", "gate", "out")]
    + ["MultiHeadAttention_0/%s/scale" % n for n in ("q_norm", "k_norm")]
    + [n + "/scale" for n in ("RMSNorm_0", "RMSNorm_1", "attn_out_norm",
                              "mlp_out_norm")])
DENSE_LEAVES = ["mlp/%s_proj/kernel" % n for n in ("gate", "up", "down")]
# (the choice bias only chooses: no gradient reaches it)
MOE_LEAVES = (["moe/" + n for n in ("router", "gate_proj", "up_proj",
                                    "down_proj")]
              + ["moe/shared/%s_proj/kernel" % n
                 for n in ("gate", "up", "down")])
LEAVES = sorted(
    ["embed/embedding", "final_ln/scale", "lm_head/kernel"]
    + ["layer_0/" + leaf for leaf in ATTN_LEAVES + DENSE_LEAVES]
    + ["layer_%d/%s" % (i, leaf) for i in range(1, 5)
       for leaf in ATTN_LEAVES + MOE_LEAVES])


# ------------------------------------------------------------- the preset

def test_the_cut_gives_published_layers_1_to_5(tiny, monkeypatch):
    cfg, _, params, _, batch = tiny
    full = lm.LMConfig.trinity_mini_26b_a3b()
    assert full.window_layers == full.rope_layers == (1, 1, 1, 0) * 8
    assert cfg.window_layers == cfg.rope_layers == full.window_layers[1:6]
    assert set(flat(params)) == {"params/" + leaf for leaf in LEAVES} | {
        "params/layer_%d/moe/e_score_correction_bias" % i
        for i in range(1, 5)}
    p = params["params"]["layer_1"]
    a = p["MultiHeadAttention_0"]
    assert a["query"]["kernel"].shape == a["gate"]["kernel"].shape \
        == (64, 16, 8)
    assert a["key"]["kernel"].shape == (64, 2, 8)
    assert a["q_norm"]["scale"].shape == a["k_norm"]["scale"].shape == (8,)
    assert p["moe"]["gate_proj"].shape == (4, 64, 24)
    assert p["moe"]["router"].shape == (64, 16)
    assert p["moe"]["shared"]["up_proj"]["kernel"].shape == (64, 24)
    assert params["params"]["layer_0"]["mlp"]["up_proj"]["kernel"].shape \
        == (64, 96)
    built = []      # what ``TransformerLM._block`` hands each layer's block
    monkeypatch.setattr(lm, "TransformerBlock", lambda *a, **kw: (
        built.append(kw), layers.TransformerBlock(*a, **kw))[1])
    jax.eval_shape(lambda p, ids: lm.TransformerLM(cfg).apply(
        p, ids, mutable=["losses", "counters"]), params, batch["tokens"])
    assert [b.get("window") for b in built] == [WINDOW, WINDOW, None, WINDOW,
                                                WINDOW]
    assert [b["rope_theta"] for b in built] == [1e4, 1e4, None, 1e4, 1e4]
    assert all(b["gated_attention"] and b["sandwich_norm"]
               and b["qk_head_norm"] and b["num_kv_heads"] == 2
               for b in built)
    assert [b.get("dense_dim", 0) for b in built] == [96, 0, 0, 0, 0]
    routers = [b["router"] for b in built[1:]]
    assert all(r == layers.RouterConfig("sigmoid", True, 2.826, 1, HELD)
               for r in routers)


def test_the_published_preset_is_the_catalogs_row():
    cfg = lm.LMConfig.trinity_mini_26b_a3b()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.max_seq_len) == (
        32, 2048, 32, 4, 128, 200192, 131072)
    assert (cfg.mlp_dim, cfg.dense_dim, cfg.first_k_dense_replace,
            cfg.num_experts, cfg.experts_per_token, cfg.num_shared_experts,
            cfg.sliding_window, cfg.rope_theta) == (
        1024, 6144, 2, 128, 8, 1, 2048, 10000.0)
    assert (cfg.router_activation, cfg.moe_renormalize,
            cfg.routed_scaling_factor, cfg.router_aux_loss_coef,
            cfg.router_z_loss_coef) == ("sigmoid", True, 2.826, 0.0, 0.0)
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-5
    assert cfg.gated_attention and cfg.qk_head_norm and cfg.sandwich_norm \
        and cfg.embed_scale
    assert not (cfg.attention_bias or cfg.head_bias or cfg.tie_embedding
                or cfg.qk_norm or cfg.router_reads_mixer_input)
    assert cfg.window_layers.count(0) == 8 and cfg.experts_held is None
    assert lm.gated_attention_layer_indices(cfg) == tuple(range(32))
    assert lm.gated_attention_layer_indices(lm.LMConfig.tiny()) == ()


def test_a_gate_needs_a_softmax_attention_layer():
    with pytest.raises(ValueError, match="gated_attention"):
        lm.LMConfig.tiny(gated_attention=True, layer_types=("conv", "conv"),
                         conv_size=3)
    assert lm.LMConfig.tiny(gated_attention=True).gated_attention


# ------------------------------------------------ against the reference

def test_logits_match_the_reference(tiny):
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        close(jax.jit(apply_fn)(params, ids),
              jax.jit(reference_logits)(params, ids))


def test_loss_matches_the_reference_and_is_the_nll_alone(loss_and_grads):
    got, want, _, _ = loss_and_grads
    close(got, want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(loss_and_grads, leaf):
    _, _, got, want = loss_and_grads
    assert np.abs(want["params/" + leaf]).max() > 0
    close(got["params/" + leaf], want["params/" + leaf])


def test_no_gradient_reaches_the_choice_bias(loss_and_grads):
    _, _, got, want = loss_and_grads
    for i in range(1, 5):
        name = "params/layer_%d/moe/e_score_correction_bias" % i
        assert not np.any(got[name]) and not np.any(want[name])


def test_two_adam_steps_follow_the_reference(tiny):
    """The driver's check at a tiny size: the losses of step 0 and of step
    1 after one Adam(1e-3) step, against ``train_check``'s."""
    _, loss_fn, params, _, _ = tiny
    b0, b1 = batches(2, seed=7)
    opt = optax.adam(1e-3)
    with jax.default_matmul_precision("highest"):
        loss0, grads = jax.jit(jax.value_and_grad(loss_fn))(params, b0)
        updates, _ = opt.update(grads, opt.init(params), params)
        loss1 = jax.jit(loss_fn)(optax.apply_updates(params, updates), b1)
        want0, want1 = ref.train_check(
            lambda p, b: ref.nll_sum(p, b, TOP_K, HELD, WINDOW),
            ref.batch_weight, params, b0, b1, jax.devices())
    close(loss0, want0)
    close(loss1, want1)


def test_the_step_on_the_kernels_is_the_references(tiny, small_tiles):
    """``attention="flash"``: every layer's core through the kernels
    (interpreted here), the window layers' through the windowed tile table,
    at tiles the sequence spans three of, THE GATE ON: loss and every
    gradient leaf."""
    cfg, _, params, _, batch = tiny
    on_kernels, _, _, _ = lm.make_train_setup(
        cfg, seq_len=SEQ, batch_size=2, seed=0, attention="flash")
    before = tel.counters().get("attention.window_tiles", 0)
    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(on_kernels))(params, batch)
        want, want_grads = jax.jit(jax.value_and_grad(reference_loss))(
            params, batch)
    close(value, want)
    for name, g in flat(grads).items():
        close(g, flat(want_grads)[name])
    # four window layers, a forward and a backward launch each, 5 of the 6
    # causal tiles: the tile behind the window is not walked
    assert tel.counters()["attention.window_tiles"] - before == 4 * 2 * 5


@pytest.mark.parametrize("fault", sorted(
    set(tool.faults()) - {"no_step", "computed_in_bfloat16",
                          "computed_in_float8_e4m3fn",
                          "float8_e4m3fn_operands_float32_cotangents"}))
def test_a_planted_fault_fails_at_the_tiny_size(tiny, fault):
    """What ``tools/loss_limit_afmoe.py`` plants into the reference is
    another model at 1e-5: the program no longer matches it."""
    _, _, params, apply_fn, batch = tiny
    ids = batch["tokens"][:, :-1]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(apply_fn)(params, ids)
        with tool.faults()[fault]():
            faulty = jax.jit(
                lambda p, i: ref.logits_fn(p, i, TOP_K, HELD, WINDOW))(
                params, ids)
    with pytest.raises(AssertionError):
        close(got, faulty)
    assert float(jnp.max(jnp.abs(got - faulty))) \
        > 1e-4 * float(jnp.max(jnp.abs(got)))


def test_serving_refuses_the_window_layers_and_grouped_heads_by_name(tiny):
    """Layer 0 has a window over grouped K/V heads: the refusal of R-M6
    stands as it was, gate or no gate."""
    cfg, _, params, _, batch = tiny
    with pytest.raises(NotImplementedError, match="sliding-window layer"):
        lm.TransformerLM(cfg).apply(
            params, batch["tokens"][:, :8], jnp.full((2,), 8),
            method=lm.TransformerLM.prefill)


# ----------------------------------------------------------------- the gate

def attention_layer(gated, **kw):
    module = layers.MultiHeadAttention(4, 8, use_bias=False, gated=gated,
                                       **kw)
    x = jnp.asarray(np.random.RandomState(2).randn(2, 12, 32), jnp.float32)
    mask = layers.causal_mask(12)
    return module, x, mask, module.init(jax.random.PRNGKey(1), x, mask)


def test_the_gate_is_o_times_sigmoid_of_the_inputs_projection():
    """By hand: the core's output (from an ungated twin whose output
    projection is the identity), times ``sigmoid(x W_g)``, through
    ``W_o``."""
    gated, x, mask, params = attention_layer(True)
    plain = layers.MultiHeadAttention(4, 8, use_bias=False)
    p = params["params"]
    assert set(p) == {"query", "key", "value", "gate", "out"}
    assert p["gate"]["kernel"].shape == (32, 4, 8)
    # the core's output o [B, S, H, D]: the plain layer under an identity
    # output projection [H, D, H * D]
    eye = jnp.eye(32).reshape(4, 8, 32)
    core_params = {"params": {k: p[k] for k in ("query", "key", "value")}
                   | {"out": {"kernel": eye}}}
    with jax.default_matmul_precision("highest"):
        o = plain.apply(core_params, x, mask).reshape(2, 12, 4, 8)
        g = jnp.einsum("bsd,dhk->bshk", x, p["gate"]["kernel"])
        want = jnp.einsum("bshk,hkd->bsd", o * jax.nn.sigmoid(g),
                          p["out"]["kernel"])
        got = gated.apply(params, x, mask)
        ungated = plain.apply({"params": {k: v for k, v in p.items()
                                          if k != "gate"}}, x, mask)
    close(got, want)
    assert float(jnp.max(jnp.abs(got - ungated))) > 1e-2


def test_the_gate_is_in_every_mode_that_shares_the_parameters():
    """Prefill returns the gated output with its K/V rows, and a cached
    decode step of the last position gives that position's row."""
    gated, x, mask, params = attention_layer(True)
    with jax.default_matmul_precision("highest"):
        full = gated.apply(params, x, mask)
        out, (k, v) = gated.apply(params, x, mask, return_kv=True)
        pad = [(0, 0), (0, 4), (0, 0), (0, 0)]
        step, _ = gated.apply(
            params, x[:, -1:], cache=(jnp.pad(k, pad), jnp.pad(v, pad)),
            cursor=jnp.full((2,), 11, jnp.int32))
    close(out, full)
    close(step[:, 0], full[:, -1])


def test_the_gates_work_is_under_attn_gate_and_outside_attn_core(tiny):
    """The projection's product, the sigmoid and the multiply carry
    ``attention/attn_gate`` and not ``attn_core``, forward and backward;
    ``out``'s product lies outside."""
    cfg, loss_fn, params, _, batch = tiny
    text = jax.jit(jax.grad(loss_fn)).lower(params, batch).as_text(
        debug_info=True)
    names = set()
    for line in text.splitlines():
        if "loc(" in line and "blocks/" in line:
            names.update(part for part in line.split('"') if "blocks/" in part)
    gate = [n for n in names if scopes.ATTN_GATE in n.split("/")]
    assert gate and all(scopes.ATTENTION in n.split("/") for n in gate)
    assert not [n for n in gate if scopes.ATTN_CORE in n.split("/")]
    for op in ("dot_general", "logistic", "mul"):
        assert [n for n in gate if n.endswith(op)], op
    assert [n for n in gate if "transpose(" in n]          # backward too
    assert all("layer_%d" % i in " ".join(gate) for i in range(5))
    assert scopes.ATTN_GATE in scopes.SCOPES
    # a model without the gate names nothing under the scope
    plain, p2, _, _ = lm.make_train_setup(
        dataclasses.replace(cfg, gated_attention=False), seq_len=SEQ,
        batch_size=2, seed=0)
    assert scopes.ATTN_GATE + "/" not in jax.jit(plain).lower(
        p2, batch).as_text(debug_info=True)


# ------------------------------- the window's convention and the kernels

@pytest.mark.parametrize("seq, window", [(48, 10), (48, 1), (48, 48),
                                         (40, 16), (4096, 2048)])
def test_the_windows_convention_is_brute_force_over_all_pairs(seq, window):
    """``i - j < W`` with the query counted: the reference's mask, the
    program's XLA band and the family's closed form, against a double loop's
    count (and the loop itself at the small sizes)."""
    from autodist_tpu.ops.attention import causal_band
    from benchmark.families import afmoe as family
    seen = np.asarray(ref.visible(jnp.arange(seq), seq, window))
    assert np.array_equal(seen, np.asarray(causal_band(seq, seq, window)))
    i, j = np.indices((seq, seq))
    assert np.array_equal(seen, (j <= i) & (i - j < window))
    assert family.window_pairs(seq, window) == int(seen.sum())
    if seq <= 48:
        by_hand = [[q >= k and q - k < window for k in range(seq)]
                   for q in range(seq)]
        assert np.array_equal(seen, np.asarray(by_hand))
    assert np.all(seen.sum(1) == np.minimum(np.arange(seq) + 1, window))


@pytest.mark.parametrize("window", [7, 16, 20, 33])
def test_the_windowed_kernels_under_the_gate_are_the_xla_path(window,
                                                              small_tiles):
    """Output and every gradient of ``(flash(q, k, v) * sigmoid(g))`` with
    the kernels (interpret mode) under a window that is, and is not, a
    multiple of the 16-row tile, 8 query heads a K/V head, against the XLA
    path under the same gate."""
    r = np.random.RandomState(window)
    q, g = (jnp.asarray(r.randn(2, SEQ, 16, 8), jnp.float32) for _ in "qg")
    k, v = (jnp.asarray(r.randn(2, SEQ, 2, 8), jnp.float32) for _ in "kv")
    do = jnp.asarray(r.randn(2, SEQ, 16, 8), jnp.float32)

    def grads(core):
        out, vjp = jax.vjp(
            lambda q, k, v, g: core(q, k, v) * jax.nn.sigmoid(g), q, k, v, g)
        return (out,) + vjp(do)
    with jax.default_matmul_precision("highest"):
        got = grads(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window))
        want = grads(lambda q, k, v: xla_windowed(q, k, v, window))
    for a, b in zip(got, want):
        close(a, b)


def test_the_cells_launch_walks_150_of_528_tiles():
    """16,384 positions, 512-row tiles, a window of 2,048: 28.4 % of the
    causal tiles for 23.4 % of the causal pairs."""
    table = fa._tile_table(32, 32, 512, 512, True, False, 2048)
    assert table.shape[1] == 150
    assert fa._tile_table(32, 32, 512, 512, True, False).shape[1] == 528
    from benchmark.families import afmoe as family
    assert family.window_pairs(16384, 2048) == 31458304
    assert family.window_pairs(16384, 2048) / family.causal_pairs(16384) \
        == pytest.approx(0.2344, abs=1e-4)


# ------------------------------------------------- the share of the experts

def program_share(x, m, held, top_k=TOP_K):
    """The routed part one chip computes: its stacks hold ``held`` only;
    sigmoid scores over ALL the router's outputs, gates renormalised over
    the chosen x 2.826."""
    idx = jnp.asarray(held)
    return expert.dropless_moe_ffn(
        x, m["router"], m["gate_proj"][idx], m["up_proj"][idx],
        m["down_proj"][idx], top_k, routing=expert.Routing(
            "sigmoid", True, ref.ROUTE_SCALE, m["e_score_correction_bias"]),
        held=tuple(held))


def test_the_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test at the published counts: 128 experts over 16
    chips of 8, 8 a token. The routed outputs of the sixteen shares,
    summed, plus the shared expert counted ONCE, equal the reference's
    whole layer with every expert held; and a share is the reference's same
    share."""
    x, m = routed_layer(np.random.RandomState(3), 48, 32, 16, 128)
    shares = [tuple(range(8 * c, 8 * c + 8)) for c in range(16)]
    with jax.default_matmul_precision("highest"):
        parts = [program_share(x, m, held, 8) for held in shares]
        uncut = ref.routed_ffn(x, m, 8, held=tuple(range(128)))
        shared = ref.swiglu(x, m["shared"])
        for held, (out, lb, z, counts) in list(zip(shares, parts))[:2]:
            idx = jnp.asarray(held)
            cut = {k: (v[idx] if k.endswith("_proj") else v)
                   for k, v in m.items()}
            close(out, ref.routed_ffn(x, cut, 8, held, shared=False))
            assert float(lb) == float(z) == 0.0 and counts.shape == (8,)
    close(sum(p[0] for p in parts) + shared, uncut)
    # every chosen pair is held by exactly one chip
    assert sum(int(jnp.sum(p[3])) for p in parts) == 48 * 8


def test_the_references_gates_sum_to_the_route_scale():
    r = np.random.RandomState(5)
    scores = jax.nn.sigmoid(jnp.asarray(r.randn(6, 16), jnp.float32))
    weight = ref.routing(scores, jnp.zeros((16,)), 3)
    assert np.all(np.count_nonzero(np.asarray(weight), axis=-1) == 3)
    close(jnp.sum(weight, -1), np.full(6, 2.826))
    _, gate, chosen = expert.Routing("sigmoid", True, 2.826,
                                     jnp.zeros((16,))).choose(
        jnp.log(scores / (1 - scores)), 3)
    close(jnp.take_along_axis(weight, chosen, axis=-1), gate)
