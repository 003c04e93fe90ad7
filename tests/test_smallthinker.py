"""SmallThinker-21BA3B-Instruct on the normal training path
(``LMConfig.smallthinker_21b_a3b``): sliding-window rotary and global NoPE
grouped-query attention layers 3 : 1, a router that reads the attention's
normed input, a share of softmax-routed ReGLU experts, against the plain
float32 reference ``benchmark/reference/smallthinker.py`` at a tiny size,
the sequence LONGER than the tiny window. ``tests/test_smallthinker_cell.py``
has the configuration file, the closed forms and the cell's record.

Tolerances. Program and reference are both float32 on the CPU here and
differ in the ORDER of sums (a fused rsqrt against a divide by sqrt, one
einsum over all held experts against one expert after another, K/V heads
repeated against indexed, the kernels' online softmax a tile at a time
against one softmax a row). ``RTOL`` 1e-5 of the largest entry holds
logits, loss and EVERY gradient leaf of the four-layer model: a window
ignored or off by one, a rotation on the wrong layer, the router on the
other norm, another gate activation or a bfloat16 matmul misses by orders
of magnitude, and each is planted below and seen.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.models import layers, lm
from autodist_tpu.ops import flash_attention as fa
from autodist_tpu.ops.attention import reference_attention
from autodist_tpu.parallel import expert
from autodist_tpu.telemetry import spans as tel
from benchmark.reference import smallthinker as ref
from benchmark.tools import loss_limit_smallthinker as tool
from tests.test_kimi_linear import close, flat

TOP_K = 3
HELD = (0, 1, 2, 3)
SEQ = 48
WINDOW = 10      # shorter than SEQ, and no multiple of the 16-row test tile
ROWS = 16        # the kernels' tile in the tests below: SEQ spans three


def tiny_config(**kw):
    """The cell's four layers (G W W W) at d 56: 14 query heads over 2 K/V
    heads of 8 (groups of SEVEN, as published), 16 experts of width 24 of
    which 4 are held, top-3 renormalised, a window of 10, theta 1.5e6, an
    untied table of 256 rows."""
    sizes = dict(vocab_size=256, d_model=56, num_heads=14, num_kv_heads=2,
                 head_dim=8, mlp_dim=24, num_experts=16,
                 experts_per_token=TOP_K, experts_held=HELD,
                 sliding_window=WINDOW)
    sizes.update(kw)
    return dataclasses.replace(
        lm.LMConfig.smallthinker_21b_a3b(
            num_layers=sizes.pop("num_layers", 4), max_seq_len=64), **sizes)


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


LAYER_LEAVES = (
    ["MultiHeadAttention_0/%s/kernel" % n
     for n in ("query", "key", "value", "out")]
    + ["moe/" + n for n in ("router", "gate_proj", "up_proj", "down_proj")]
    + ["RMSNorm_0/scale", "RMSNorm_1/scale"])
LEAVES = sorted(
    ["embed/embedding", "final_ln/scale", "lm_head/kernel"]
    + ["layer_%d/%s" % (i, leaf) for i in range(4) for leaf in LAYER_LEAVES])


# ------------------------------------------------------------- the preset

def test_the_published_layouts_give_layers_0_to_3_one_period(tiny,
                                                             monkeypatch):
    cfg, _, params, _, batch = tiny
    full = lm.LMConfig.smallthinker_21b_a3b()
    assert full.window_layers == full.rope_layers == (0, 1, 1, 1) * 13
    assert cfg.window_layers == cfg.rope_layers == full.window_layers[:4]
    assert set(flat(params)) == {"params/" + leaf for leaf in LEAVES}
    p = params["params"]["layer_1"]
    assert p["MultiHeadAttention_0"]["query"]["kernel"].shape == (56, 14, 8)
    assert p["MultiHeadAttention_0"]["key"]["kernel"].shape == (56, 2, 8)
    assert p["moe"]["gate_proj"].shape == (4, 56, 24)
    assert p["moe"]["router"].shape == (56, 16)
    built = []      # what ``TransformerLM._block`` hands each layer's block
    monkeypatch.setattr(lm, "TransformerBlock", lambda *a, **kw: (
        built.append(kw), layers.TransformerBlock(*a, **kw))[1])
    jax.eval_shape(lambda p, ids: lm.TransformerLM(cfg).apply(
        p, ids, mutable=["losses", "counters"]), params, batch["tokens"])
    assert [b.get("window") for b in built] == [None, WINDOW, WINDOW, WINDOW]
    assert [b["rope_theta"] for b in built] == [None, 1.5e6, 1.5e6, 1.5e6]
    assert all(b["router"].reads_mixer_input
               and b["router"].gate_activation == "relu" for b in built)


def test_the_published_preset_is_the_catalogs_row():
    cfg = lm.LMConfig.smallthinker_21b_a3b()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.max_seq_len) == (
        52, 2560, 28, 4, 128, 151936, 16384)
    assert (cfg.mlp_dim, cfg.num_experts, cfg.experts_per_token,
            cfg.sliding_window, cfg.rope_theta) == (768, 64, 6, 4096, 1.5e6)
    assert (cfg.router_activation, cfg.moe_renormalize,
            cfg.routed_scaling_factor, cfg.num_shared_experts,
            cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (
        "softmax", True, 1.0, 0, 0.0, 0.0)
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-6
    assert cfg.router_reads_mixer_input
    assert cfg.expert_gate_activation == "relu" and cfg.expert_gated
    assert not (cfg.attention_bias or cfg.head_bias or cfg.embed_scale
                or cfg.tie_embedding or cfg.qk_norm or cfg.qk_head_norm)
    assert cfg.window_layers.count(0) == 13 and cfg.first_k_dense_replace == 0


@pytest.mark.parametrize("bad", [
    dict(window_layers=(0, 1)),                         # not a flag a layer
    dict(window_layers=(0, 2, 1, 1)),                   # not 0 / 1
    dict(sliding_window=0),                             # flagged, no window
    dict(window_layers=(0, 0, 0, 0)),                   # a window, no layer
    dict(rope_layers=(0, 1, 1, 1), rope_theta=None),    # nothing to rotate by
    dict(expert_gate_activation="gelu"),
    dict(router_reads_mixer_input=True, num_experts=0, experts_per_token=0,
         moe_renormalize=False, experts_held=None,
         expert_gate_activation="silu"),
    dict(num_shared_experts=1),         # the shared SwiGLU's gate is SiLU
], ids=["short_layout", "flag_2", "no_window", "no_window_layer",
        "no_theta", "gelu", "no_experts", "shared_expert"])
def test_a_config_that_names_what_is_not_built_is_refused(bad):
    with pytest.raises(ValueError):
        tiny_config(**bad)


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
    at tiles the sequence spans three of: loss and every gradient leaf."""
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
    # three window layers, a forward and a backward launch each, 5 of the 6
    # causal tiles: the tile behind the window is not walked
    assert tel.counters()["attention.window_tiles"] - before == 3 * 2 * 5


def test_the_lean_head_and_the_plain_head_agree(tiny, loss_and_grads):
    """The cell's logits (16,384 x 18,992 x 4 B) pass
    ``LEAN_HEAD_LOGIT_BYTES`` and take the chunked head; the tiny model's
    take the plain one."""
    cfg, _, params, _, batch = tiny
    lean, _, _, _ = lm.make_train_setup(cfg, seq_len=SEQ, batch_size=2,
                                        seed=0, lean_head=True)
    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(lean))(params, batch)
    got_loss, _, got, _ = loss_and_grads
    close(value, got_loss)
    for name, g in flat(grads).items():
        close(g, got[name])
    assert 4 * 16384 * 18992 >= lm.LEAN_HEAD_LOGIT_BYTES > 18992


@pytest.mark.parametrize("fault", [
    "window_ignored", "window_off_by_one", "rotation_on_the_global_layer",
    "no_rotation_on_a_window_layer", "router_reads_the_second_norm",
    "silu_for_relu", "gates_not_renormalised",
    "every_query_head_on_kv_head_0", "one_held_expert_lost"])
def test_a_planted_fault_fails_at_the_tiny_size(tiny, fault):
    """What ``tools/loss_limit_smallthinker.py`` plants into the reference
    is another model at 1e-5: the program no longer matches it."""
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


def test_serving_refuses_a_window_layer_by_name(tiny):
    cfg, _, params, _, batch = tiny
    model = lm.TransformerLM(cfg)
    # (layer 0 is global over grouped K/V heads: refused as those are)
    with pytest.raises(NotImplementedError, match="grouped K/V heads"):
        model.apply(params, batch["tokens"][:, :8], jnp.full((2,), 8),
                    method=lm.TransformerLM.prefill)
    block = layers.TransformerBlock(2, 8, 16, norm="rmsnorm", window=4)
    x = jnp.zeros((1, 8, 16))
    p = block.init(jax.random.PRNGKey(0), x)
    with pytest.raises(NotImplementedError, match="sliding-window layer"):
        block.apply(p, x, return_kv=True)
    cache = jnp.zeros((1, 8, 2, 8))
    with pytest.raises(NotImplementedError, match="sliding-window layer"):
        block.apply(p, x[:, :1], cache=(cache, cache),
                    cursor=jnp.zeros((1,), jnp.int32))


# -------------------------------------------------- the windowed kernels

def xla_windowed(q, k, v, window):
    """The XLA path's scores under the band's mask, K/V heads repeated."""
    S, group = q.shape[1], q.shape[2] // k.shape[2]
    rows, cols = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = ((rows >= cols) & (rows - cols < window))[None, None]
    return reference_attention(q, jnp.repeat(k, group, 2),
                               jnp.repeat(v, group, 2), mask)


@pytest.mark.parametrize("window", [1, 7, 16, 20, 32, 33, 47, 48, 100])
def test_the_windowed_kernels_are_the_xla_path(window, small_tiles):
    """Output and dq / dk / dv of the kernels (interpret mode) under a
    window that is, and is not, a multiple of the 16-row tile, 7 query
    heads a K/V head, at a sequence of three tiles."""
    r = np.random.RandomState(window)
    q = jnp.asarray(r.randn(2, SEQ, 14, 8), jnp.float32)
    k, v = (jnp.asarray(r.randn(2, SEQ, 2, 8), jnp.float32) for _ in "kv")
    do = jnp.asarray(r.randn(2, SEQ, 14, 8), jnp.float32)

    def grads(f):
        out, vjp = jax.vjp(f, q, k, v)
        return (out,) + vjp(do)
    with jax.default_matmul_precision("highest"):
        got = grads(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window))
        want = grads(lambda q, k, v: xla_windowed(q, k, v, window))
    for g, w in zip(got, want):
        if np.any(np.asarray(w)):
            close(g, w)
        else:   # a window of 1: one key a query, no gradient to the scores
            assert float(jnp.max(jnp.abs(g))) < 1e-5


def test_the_split_backward_walks_the_window_too(small_tiles, monkeypatch):
    """``flash_dq`` / ``flash_dkdv`` (a head whose dq accumulator does not
    fit) under a window: the same gradients as the fused kernel's."""
    r = np.random.RandomState(5)
    q = jnp.asarray(r.randn(1, SEQ, 7, 8), jnp.float32)
    k, v = (jnp.asarray(r.randn(1, SEQ, 1, 8), jnp.float32) for _ in "kv")
    loss = lambda *a: jnp.sum(jnp.sin(fa.flash_attention(  # noqa: E731
        *a, causal=True, window=20)))
    fused = jax.grad(loss, (0, 1, 2))(q, k, v)
    monkeypatch.setattr(fa, "_VMEM", 0)
    before = tel.counters().get("attention.flash_bwd_split", 0)
    split = jax.grad(loss, (0, 1, 2))(q, k, v)
    assert tel.counters()["attention.flash_bwd_split"] == before + 1
    for a, b in zip(fused, split):
        close(a, b, 1e-6)


def test_a_window_needs_a_causal_call_and_a_whole_one_is_none():
    q = jnp.zeros((1, 32, 2, 8))
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, causal=True, window=0)
    before = dict(tel.counters())
    fa.flash_attention(q, q, q, causal=True, window=32)   # every earlier key
    assert tel.counters().get("attention.window_tiles", 0) \
        == before.get("attention.window_tiles", 0)


# ---------------------------------------------------------- the tile table

CASES = [(n, b, w, kv_major) for n, b, w in (
    (6, 16, 10), (6, 16, 16), (6, 16, 17), (6, 16, 32), (6, 16, 33),
    (6, 16, 1), (6, 16, 96), (4, 8, 9), (32, 512, 4096), (32, 512, 4097),
    (16, 512, 4096)) for kv_major in (False, True)]


@pytest.mark.parametrize("n,block,window,kv_major", CASES)
def test_the_tile_table_is_brute_force_over_all_pairs(n, block, window,
                                                      kv_major):
    """Every tile that holds a visible (i, j) once, no dead tile,
    ``CROSSED`` exactly on the tiles that also hold an invisible pair, the
    first / last bits where the walk says, and ``_Q_OUT`` naming each q
    block over one run of steps that ends at its last tile."""
    # every (i, j) pair, a byte each: ``i - j < window`` as ``j > i - window``
    # makes no [S, S] array of differences (a gigabyte at 16,384 positions)
    i = np.arange(n * block, dtype=np.int32)[:, None]
    j = i.T
    seen = (j <= i) & (j > i - window)
    tiles = seen.reshape(n, block, n, block)
    live = tiles.any(axis=(1, 3))
    partly = live & ~tiles.all(axis=(1, 3))
    table = fa._tile_table(n, n, block, block, True, kv_major, window)
    q, kv, flags, q_out = table
    assert sorted(zip(q, kv)) == sorted(zip(*np.nonzero(live)))
    assert np.array_equal((flags & fa.CROSSED) != 0, partly[q, kv])
    outer = kv if kv_major else q
    assert np.all(np.diff(outer) >= 0)         # a block's tiles in one run
    steps = np.arange(q.size)
    for block_of, first, last in ((q, fa.Q_FIRST, fa.Q_LAST),
                                  (kv, fa.KV_FIRST, fa.KV_LAST)):
        for b in range(n):
            mine = steps[block_of == b]
            assert np.array_equal(steps[(flags & first != 0)
                                        & (block_of == b)], mine[:1])
            assert np.array_equal(steps[(flags & last != 0)
                                        & (block_of == b)], mine[-1:])
    for b in range(n):
        named = steps[q_out == b]
        last = steps[q == b][-1]
        assert named[-1] == last                   # until its last tile ...
        assert np.array_equal(named, np.arange(named[0], last + 1))  # one run
    if window >= n * block:     # no window at all: the causal table itself
        assert np.array_equal(
            table, fa._tile_table(n, n, block, block, True, kv_major))


def test_the_cells_launch_walks_252_of_528_tiles():
    """16,384 positions, 512-row tiles, a window of 4,096: 47.7 % of the
    causal tiles for 43.75 % of the causal pairs; 56 tiles pay a compare
    (the diagonal's 32 and the far edge's 24)."""
    table = fa._tile_table(32, 32, 512, 512, True, False, 4096)
    assert table.shape[1] == 252
    assert fa._tile_table(32, 32, 512, 512, True, False).shape[1] == 528
    assert np.count_nonzero(table[fa._FLAGS] & fa.CROSSED) == 56
    before = dict(tel.counters())
    fa._table(16384, 16384, 512, 512, True, True, 4096)
    moved = {k: tel.counters()[k] - before.get(k, 0) for k in (
        "attention.window_tiles", "attention.window_tiles_causal",
        "attention.flash_tiles")}
    assert moved == {"attention.window_tiles": 252,
                     "attention.window_tiles_causal": 528,
                     "attention.flash_tiles": 252}


# ------------------------------------------------- the share of the experts

def routed_layer(rng, tokens, d, f, n_all):
    w = lambda *s: jnp.asarray(rng.randn(*s) / np.sqrt(s[-2]), jnp.float32)  # noqa: E731
    m = {"router": w(d, n_all), "gate_proj": w(n_all, d, f),
         "up_proj": w(n_all, d, f), "down_proj": w(n_all, f, d)}
    x = lambda: jnp.asarray(rng.randn(tokens, d), jnp.float32)  # noqa: E731
    return x(), x(), m


def program_share(u, h, m, held, top_k=TOP_K):
    """The routed part one chip computes: its stacks hold ``held`` only,
    the router reads h, the experts u; softmax over ALL the router's
    outputs, gates renormalised over the chosen, ReGLU."""
    idx = jnp.asarray(held)
    return expert.dropless_moe_ffn(
        u[None], m["router"], m["gate_proj"][idx], m["up_proj"][idx],
        m["down_proj"][idx], top_k, jnp.float32,
        expert.Routing("softmax", True), held=tuple(held),
        router_input=h[None], gate_activation="relu")


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: 64 experts over 8 chips of 8 (the
    deployment's 8 ways). The routed outputs of the eight shares, summed,
    equal the reference's whole layer with every expert held; and each
    share is the reference's same share."""
    u, h, m = routed_layer(np.random.RandomState(3), 48, 32, 16, 64)
    shares = [tuple(range(8 * c, 8 * c + 8)) for c in range(8)]
    with jax.default_matmul_precision("highest"):
        logits = ref.mm(h, m["router"])
        parts = [program_share(u, h, m, held) for held in shares]
        uncut = ref.routed_ffn(u, logits, m, TOP_K, held=tuple(range(64)))
        for held, (out, _, _, counts) in zip(shares[:3], parts):
            idx = jnp.asarray(held)
            cut = {k: (v[idx] if k.endswith("_proj") else v)
                   for k, v in m.items()}
            close(out[0], ref.routed_ffn(u, logits, cut, TOP_K, held))
            assert counts.shape == (8,)
    close(sum(p[0][0] for p in parts), uncut)
    # every chosen pair is held by exactly one chip
    assert sum(int(jnp.sum(p[3])) for p in parts) == 48 * TOP_K


def test_every_expert_held_is_the_sorted_form_with_a_relu_gate():
    """``held=None`` takes the grouped matmuls (OLMoE's form) with the
    router on another operand and ``relu`` for SiLU."""
    u, h, m = routed_layer(np.random.RandomState(6), 16, 32, 16, 4)
    with jax.default_matmul_precision("highest"):
        out, _, _, counts = expert.dropless_moe_ffn(
            u[None], m["router"], m["gate_proj"], m["up_proj"],
            m["down_proj"], 2, jnp.float32, expert.Routing("softmax", True),
            router_input=h[None], gate_activation="relu")
        close(out[0], ref.routed_ffn(u, ref.mm(h, m["router"]), m, 2))
    assert int(jnp.sum(counts)) == 16 * 2
    with pytest.raises(ValueError, match="gate_activation"):
        expert.dropless_moe_ffn(
            u[None], m["router"], m["gate_proj"], m["up_proj"],
            m["down_proj"], 2, gate_activation="gelu")


def test_the_router_reads_the_mixers_input_and_the_experts_do_not():
    """Moving h alone moves the choice and the gates; the experts' rows
    are u's: with h = u the layer is the one every other preset builds."""
    u, h, m = routed_layer(np.random.RandomState(8), 16, 32, 16, 8)
    args = (m["router"], m["gate_proj"], m["up_proj"], m["down_proj"], 2,
            jnp.float32, expert.Routing("softmax", True))
    with jax.default_matmul_precision("highest"):
        same = expert.dropless_moe_ffn(u[None], *args, router_input=u[None])
        none = expert.dropless_moe_ffn(u[None], *args)
        other = expert.dropless_moe_ffn(u[None], *args, router_input=h[None])
    assert np.array_equal(same[0], none[0])
    assert float(jnp.max(jnp.abs(other[0] - none[0]))) > 1e-3
