"""Expert parallelism — Mixture-of-Experts feed-forwards, two routings.

Beyond the reference (data-parallel only, reference
``docs/design/architecture.rst:46-48``). Expert weights are stacked on a
leading dim (``[E, d, f]`` / ``[E, f, d]``) so they can shard over the
``expert`` mesh axis (``VarConfig.mp_axes = {0: 'expert'}``). Which path
runs when:

- :func:`dropless_moe_ffn` — token-choice **top-k, no token dropped**,
  wherever all experts are local: one device, or data-parallel replicas
  each holding every expert (how OLMoE was trained, arXiv 2409.02060). The
  T·k (token, expert) pairs are sorted by expert, the rows gathered into
  that order, the three SwiGLU projections run as ONE grouped matmul
  primitive (:func:`grouped_matmul`: the pallas ``megablox`` kernels that
  ship with JAX) over ``group_sizes``, and the outputs are un-sorted and
  summed back per token under their gates. Every shape is static
  ([T·k, d]) whatever the routing; the data-dependent part is two row
  gathers by a permutation, whose backward passes are the inverse
  permutation's gathers.
  How the router scores, chooses and gates is a :class:`Routing` (softmax
  or sigmoid scores, renormalised and scaled gates, a choice-only bias);
  WHICH experts the layer holds is apart from it (``held``). A layer that
  holds a SHARE of its experts (one chip's of an expert-parallel
  deployment, run without the exchange) routes over all of them, takes its
  router losses over all of them, and runs every held expert on every
  token under its gate (:func:`_held_experts`): constant work whatever the
  router does.
- :func:`moe_ffn` — **top-1 with a fixed per-expert capacity** (Switch,
  arXiv 2101.03961; GShard, arXiv 2006.16668), the path for a BOUND
  ``expert`` axis: tokens reach their expert's owning device with one
  ``lax.all_to_all`` each way, which needs the fixed ``[E, C, d]`` layout;
  dispatch and combine are dense one-hot ``[T, E, C]`` einsums and tokens
  over capacity are dropped. With the axis unbound it computes every
  expert locally (``models/moe_lm.py``). A dropless all-to-all is not
  written yet (ROADMAP R4).

The one-hot formulation is not what a TPU needs at these sizes: at
OLMoE's shapes (T 8,192, E 64, k 8) the ``[T, E, C]`` dispatch tensor
alone would be 1.07e9 elements a layer. A dynamic gather or scatter
does not defeat XLA's tiling either: on a v5e the whole layer, forward and backward, takes 43.8 ms for 65,536 pairs of
2,048 features, 29.1 ms of it the grouped matmuls; the same layer with
the rows gathered by token and scatter-ADDED back takes 41.5 ms alone
but made the OLMoE train step 2 % SLOWER than this form (the step's
other fusions change around it), and with plain gathers, whose backward
passes are [65536, 2048] scatters, the routing alone took 23.2 ms
against 13.0 (my chip runs, PR 25; PERF.md section 6).
"""
import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as gmm_kernel
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as tgmm_kernel

from autodist_tpu import const
from autodist_tpu.ops import pallas_mode
from autodist_tpu.parallel.sequence import axis_bound
from autodist_tpu.telemetry import scopes


def top1_dispatch(router_probs, capacity: int):
    """Top-1 gating with capacity (Switch). router_probs [T, E] ->
    (dispatch [T, E, C] one-hot, combine [T, E, C] gated, aux_loss scalar).

    Tokens beyond an expert's capacity are dropped (their combine weights
    are zero -> they pass through the residual connection only).
    """
    T, E = router_probs.shape
    expert_idx = jnp.argmax(router_probs, axis=-1)               # [T]
    gate = jnp.take_along_axis(router_probs, expert_idx[:, None], 1)[:, 0]
    onehot = jax.nn.one_hot(expert_idx, E, dtype=router_probs.dtype)  # [T, E]
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0              # [T, E]
    keep = (pos >= 0) & (pos < capacity)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=router_probs.dtype)            # [T, E, C]
    dispatch = pos_oh * keep.astype(router_probs.dtype)[..., None]
    combine = dispatch * gate[:, None, None]
    # Switch aux load-balance loss: E * sum_e fraction_dispatched * mean_prob
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(router_probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def _dispatch_a2a(x_ecd, axis_name):
    """[E, C, d] (inputs for every global expert, from local tokens) ->
    [E_local, N*C, d] (this rank's experts' inputs from every rank)."""
    n = jax.lax.psum(1, axis_name)
    E, C, d = x_ecd.shape
    x = x_ecd.reshape(n, E // n, C, d)
    # tiled a2a on dim 0: rank r keeps expert-group r from EVERY source
    # rank; dim 0 of the result indexes the source rank
    x = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)                       # [n, E_local, C, d]
    x = x.transpose(1, 0, 2, 3)                              # [E_local, n, C, d]
    return x.reshape(E // n, n * C, d)


def _combine_a2a(y_elcd, axis_name, E: int):
    """Inverse of ``_dispatch_a2a``: [E_local, N*C, d] -> [E, C, d]."""
    n = jax.lax.psum(1, axis_name)
    E_local, NC, d = y_elcd.shape
    C = NC // n
    y = y_elcd.reshape(E_local, n, C, d).transpose(1, 0, 2, 3)  # [n, E_local, C, d]
    y = jax.lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)                       # [n, E_local, C, d]
    return y.reshape(E, C, d)


def moe_ffn(x, router_w, w1, b1, w2, b2,
            capacity_factor: float = 2.0,
            axis_name: str = const.EXPERT_AXIS,
            dtype=None) -> Tuple[jax.Array, jax.Array]:
    """Top-1 MoE feed-forward. Returns (output with x's shape, aux loss).

    - ``x``: [..., d] local activations; flattened to tokens internally.
    - ``router_w``: [d, E] (replicated).
    - ``w1``/``b1``/``w2``/``b2``: expert-stacked [E(, ...)] — pass the LOCAL
      shard inside the lowering ([E_local, ...]) or the full stack outside.
    - capacity C = ceil(T_local/E * capacity_factor) tokens per expert per
      rank (static).
    """
    dt = dtype or x.dtype
    d = x.shape[-1]
    lead = x.shape[:-1]
    tokens = x.reshape(-1, d)
    T = tokens.shape[0]
    bound = axis_bound(axis_name)
    n = jax.lax.psum(1, axis_name) if bound else 1
    E_local = w1.shape[0]
    E = E_local * n
    capacity = int(np.ceil(T / E * capacity_factor))

    logits = tokens.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits)
    dispatch, combine, aux = top1_dispatch(probs, capacity)
    dispatch = dispatch.astype(dt)
    combine = combine.astype(dt)

    x_ecd = jnp.einsum("td,tec->ecd", tokens, dispatch)      # [E, C, d]
    if bound:
        x_in = _dispatch_a2a(x_ecd, axis_name)               # [E_local, nC, d]
    else:
        x_in = x_ecd
    h = jnp.einsum("ecd,edf->ecf", x_in, w1.astype(dt)) + b1.astype(dt)[:, None]
    h = jax.nn.gelu(h)
    y = jnp.einsum("ecf,efd->ecd", h, w2.astype(dt)) + b2.astype(dt)[:, None]
    if bound:
        y = _combine_a2a(y, axis_name, E)                    # [E, C, d]
    out = jnp.einsum("tec,ecd->td", combine, y)
    return out.reshape(lead + (d,)), aux.astype(jnp.float32)


# ------------------------------------------------ dropless top-k routing


@jax.custom_vjp
def _permute_rows(x, perm, inv_perm):
    """``x[perm]`` for a permutation ``perm`` of x's rows. The backward
    pass of a gather is a scatter-add; of a permutation it is the gather
    by the inverse permutation, which is what this rule says."""
    return jnp.take(x, perm, axis=0)


def _permute_rows_fwd(x, perm, inv_perm):
    return jnp.take(x, perm, axis=0), (perm, inv_perm)


def _permute_rows_bwd(res, g):
    perm, inv_perm = res
    return jnp.take(g, inv_perm, axis=0), None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


# (rows, contraction, columns) tile of the grouped matmul kernels: the
# fastest of those measured on a v5e at OLMoE's shapes ([65536, 2048] x
# [64, 2048, 1024], groups as uneven as the cell's; PERF.md section 6,
# PR 25). A row tile that two groups share is visited once for each, so a
# smaller row tile also wastes less at the 63 group boundaries. The rows
# of a call must be a multiple of the row tile: it shrinks to what
# divides them.
_GMM_TILE = (256, 2048, 1024)
# the weight-gradient kernel keeps a [contraction, columns] float32 tile
# in VMEM: at 2048 x 1024 the v5e's compiler refuses the train step
_TGMM_TILE = (256, 1024, 1024)


def _tile(limit, m, k, n):
    return (math.gcd(m, limit[0]), min(k, limit[1]), min(n, limit[2]))


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group e] @ rhs[e]`` for every group e, the rows of
    ``lhs`` [M, K] sorted by group, ``rhs`` [E, K, N], ``group_sizes`` [E]
    int32 summing to M: [M, N] in lhs's dtype, float32 accumulation. The
    one grouped-matmul primitive of the tree: megablox's pallas kernels
    (compiled on a TPU, interpreted on the CPU test backend) under one
    backward rule. Both rules are traced under the CALLER's scopes."""
    return _grouped_matmul_fwd(lhs, rhs, group_sizes)[0]


def _gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return gmm_kernel(
        lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
        tiling=_tile(_GMM_TILE, lhs.shape[0], lhs.shape[1], n),
        transpose_rhs=transpose_rhs, interpret=pallas_mode.interpret())


def _grouped_matmul_fwd(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(res, g):
    lhs, rhs, group_sizes = res
    d_lhs = _gmm(g, rhs, group_sizes, transpose_rhs=True)
    d_rhs = tgmm_kernel(
        lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
        _tile(_TGMM_TILE, lhs.shape[0], lhs.shape[1], g.shape[1]),
        interpret=pallas_mode.interpret())
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def router_losses(logits, probs, counts):
    """(load-balance loss, z-loss) of one layer over the tokens it sees.

    ``L_lb = E * sum_e f_e P_e`` with ``f_e`` = routed pairs that chose e
    / T (so sum_e f_e = k) and ``P_e`` the mean router probability of e;
    ``L_z = mean_t logsumexp(logits_t)^2`` (arXiv 2409.02060 eqs. 2-3;
    HF ``load_balancing_loss_func``). ``counts`` carries no gradient."""
    T, E = probs.shape
    frac = counts.astype(jnp.float32) / T
    lb = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return lb, z


def sequence_balance_loss(probs, expert, sequences: int):
    """DeepSeek-V2's ``seq_aux`` balance loss of one layer (arXiv
    2405.04434 eqs. 12-14; the released ``MoEGate``): per sequence b of S
    tokens ``f_be = E / (k S) * #{(t, j): token t of b chose e}`` and
    ``P_be = mean_t probs_te``, ``L = mean_b sum_e f_be P_be`` (1 under an
    even router). ``probs`` [T, E] over ALL the router's outputs, ``expert``
    [T, k] the chosen ones, the T = sequences x S rows in sequence order;
    ``f`` is a count and carries no gradient."""
    T, E = probs.shape
    k, S = expert.shape[-1], T // sequences
    chose = (expert.reshape(sequences, S * k, 1)
             == jnp.arange(E)[None, None, :])                    # [B, S k, E]
    frac = jnp.sum(chose, axis=1, dtype=jnp.float32) * (E / (k * S))
    mean_prob = jnp.mean(probs.reshape(sequences, S, E), axis=1)
    return jnp.mean(jnp.sum(frac * mean_prob, axis=-1))


class Routing(NamedTuple):
    """How a router scores, chooses and gates: a score per expert
    (``activation``: the "softmax" over all experts or a "sigmoid" each),
    the top k of ``score + bias`` chosen (one group: plain top-k; the bias
    only chooses), the gates the chosen scores themselves, renormalised to
    sum to one where ``renormalize``, times ``scaling_factor``. The
    defaults are OLMoE's and DeepSeek-V2's gate (the softmax probability,
    ``norm_topk_prob`` false); Kimi-Linear's is a sigmoid with a bias,
    renormalised and scaled. Which experts a layer HOLDS is not the
    router's business (``dropless_moe_ffn``'s ``held``)."""
    activation: str = "softmax"
    renormalize: bool = False
    scaling_factor: float = 1.0
    bias: Optional[jax.Array] = None

    def choose(self, logits, top_k):
        """(scores [T, E], gate [T, k], expert [T, k]) of float32 logits."""
        scores = (jax.nn.softmax(logits, axis=-1)
                  if self.activation == "softmax" else jax.nn.sigmoid(logits))
        if self.bias is None:
            gate, expert = jax.lax.top_k(scores, top_k)
        else:
            _, expert = jax.lax.top_k(
                scores + jax.lax.stop_gradient(self.bias), top_k)
            gate = jnp.take_along_axis(scores, expert, axis=-1)
        if self.renormalize:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        if self.scaling_factor != 1.0:
            gate = gate * self.scaling_factor
        return scores, gate, expert


def dropless_moe_ffn(x, router_w, w_gate, w_up, w_down, top_k: int,
                     dtype=None, routing: Routing = Routing(),
                     held: Optional[Tuple[int, ...]] = None,
                     seq_aux: bool = False, router_input=None,
                     gate_activation: str = "silu"):
    """Token-choice top-k MoE with no token dropped, its experts gated
    (SwiGLU; ReGLU with ``gate_activation`` "relu") or, with ``w_gate``
    None, not (``relu(up x)^2``). Returns
    (output with x's shape, load-balance loss, z-loss, routed pairs per
    expert of the stacks [E] int32: the layer's load).

    - ``x``: [..., S, d] activations; flattened to T tokens internally.
    - ``router_input``: x's shape, what the router's logits are taken
      from where that is not x (a router that reads the block's first
      norm's output, the token mixer's input, while the experts read x);
      None = x.
    - ``router_w``: [d, E_all]; logits and scores in float32 over all
      E_all; ``routing`` says how they become k gates a token (by default
      the softmax probability itself, no renormalisation over the chosen
      k: HF ``norm_topk_prob`` false).
    - ``w_gate``/``w_up``: [E, d, f], ``w_down``: [E, f, d]; computed in
      ``dtype`` with float32 accumulation of the per-token combine:
      ``sum_j g_j * down_ej(silu(gate_ej(x)) * up_ej(x))``
      (``gate_activation`` "relu": ``relu(gate_ej(x))`` for the SiLU).
      ``w_gate`` None: two stacks an expert and no gate,
      ``sum_j g_j * down_ej(relu(up_ej(x))^2)`` (Nemotron-H's ``relu2``).
    - ``held``: the experts of the router's E_all whose weights the stacks
      hold, in the stacks' order (one chip's share under expert
      parallelism); None = all, in the sorted form. With a share the
      router still scores, normalises and takes its losses over all E_all
      and the output is the part the held experts give
      (:func:`_held_experts`); what absent experts would add is left out.
      Nothing stands in for the chips that hold them.
    - the router losses, of a softmax router only (a sigmoid router's are
      0): OLMoE's pair over all the tokens this call sees
      (:func:`router_losses`), or with ``seq_aux`` DeepSeek-V2's balance
      loss per sequence of x's second-to-last axis
      (:func:`sequence_balance_loss`) and no z-loss.
    """
    if gate_activation not in GATE_ACTIVATIONS:
        raise ValueError("gate_activation must be one of %s, got %r"
                         % (sorted(GATE_ACTIVATIONS), gate_activation))
    dt = dtype or x.dtype
    d = x.shape[-1]
    tokens = x.reshape(-1, d)
    routed_from = tokens if router_input is None \
        else router_input.reshape(-1, d)
    with scopes.scope(scopes.MOE):
        with scopes.scope(scopes.MOE_ROUTE):
            # true float32 (a TPU's default would round to bf16 passes):
            # [T, d] x [d, E] is small, and near-ties decide the routing
            logits = jnp.dot(routed_from.astype(jnp.float32),
                             router_w.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            probs, gate, expert = routing.choose(logits, top_k)  # [T, k]
        experts = _sorted_experts if held is None else functools.partial(
            _held_experts, held=held)
        out, counts = experts(tokens.astype(dt), gate, expert,
                              w_gate, w_up, w_down,
                              activation=GATE_ACTIVATIONS[gate_activation])
        if routing.activation != "softmax":
            lb = z = jnp.float32(0.0)
        elif seq_aux:
            with scopes.scope(scopes.MOE_ROUTE):
                lb = sequence_balance_loss(probs, expert,
                                           tokens.shape[0] // x.shape[-2])
            z = jnp.float32(0.0)
        else:   # over ALL the router's outputs, held here or not
            lb, z = router_losses(
                logits, probs, counts if held is None else _pairs_per_expert(
                    expert.reshape(-1), router_w.shape[-1]))
    return out.astype(dt).reshape(x.shape), lb, z, counts


# what a gated expert's gate passes before it multiplies the up product
GATE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _sorted_experts(tokens, gate, expert, w_gate, w_up, w_down,
                    activation=jax.nn.silu):
    """All experts held: ([T, d] float32, pairs per expert [E]) by the
    sorted form of the module docstring: the T k pairs sorted by expert,
    one grouped matmul for each projection, the un-sort and the gated
    sum."""
    dt = tokens.dtype
    (T, d), top_k, E = tokens.shape, expert.shape[-1], w_up.shape[0]
    with scopes.scope(scopes.MOE_ROUTE):
        pair_expert = expert.reshape(-1)                     # [T*k]
        order = jnp.argsort(pair_expert, stable=True)        # by expert
        inv_order = jnp.argsort(order)
        counts = _pairs_per_expert(pair_expert, E)
        pairs = jnp.repeat(tokens, top_k, axis=0)            # [T*k, d]
        xs = _permute_rows(pairs, order, inv_order)
    with scopes.scope(scopes.MOE_EXPERTS):
        product = lambda w: grouped_matmul(xs, w.astype(dt), counts)  # noqa: E731
        h = (jnp.square(jax.nn.relu(product(w_up))) if w_gate is None
             else activation(product(w_gate)) * product(w_up))
        ys = grouped_matmul(h, w_down.astype(dt), counts)    # [T*k, d]
    with scopes.scope(scopes.MOE_ROUTE):
        ys = _permute_rows(ys, inv_order, order)             # token order
        out = jnp.sum(ys.reshape(T, top_k, d).astype(jnp.float32)
                      * gate[:, :, None], axis=1)
    return out, counts


def _pairs_per_expert(pair_expert, n_experts):
    """Routed pairs per expert [E] int32, by comparison and not
    bincount's scatter-add."""
    return jnp.sum(pair_expert[:, None] == jnp.arange(n_experts)[None, :],
                   axis=0, dtype=jnp.int32)


# what a block recomputed in the backward pass keeps of the held experts by
# name (``models/lm.py:TransformerLM._block``, as ``ops/flash_attention.py:
# KEPT`` and ``ops/dsa.py:KEPT`` are kept): the gate and the up projection's
# products [T, E, f], which SiLU's and the product's backward read (experts
# without a gate: the one up product, which the squared ReLU's reads)
KEPT = "held_experts_kept"


def _held_experts(tokens, gate, expert, w_gate, w_up, w_down, held,
                  activation=jax.nn.silu):
    """The held experts' part of the routed sum, ([T, d] float32, pairs per
    held expert [E]): EVERY held expert on EVERY token, its hidden
    activations scaled by the token's gate for it (zero where the token
    did not choose it), one matmul over all E f hidden features down.

    Why not the sorted form above over the pairs that chose a held expert:
    a dropless layer's static bound is all T k pairs whatever the share, so
    that form sorts and gathers [T k, d] rows to run the grouped matmul
    over the few that are held. On the v5e at Kimi-Linear's share (8 of
    256 held, k = 8, T = 8,192) that was 110 ms a step of sort and row
    gathers in four layers around 1 to 8 ms of kernels, and the STEP'S TIME
    FOLLOWED THE ROUTING: with seeded weights under Adam every token of a
    sequence soon chooses the same experts, so a held expert got no token
    or all 8,192, and each such expert added 3.7 ms (0.5 %) to the step
    (PERF.md section 6, PR 29). Here the work is T E rows, every row of it
    real, and the same whatever the router does. Against the sorted form's
    static T k rows that is no more wherever E <= k (Kimi-Linear's share:
    8 and 8) and E / k of it otherwise: DeepSeek-V2-Lite's 8 held at k = 6
    run 1.33 x T k rows, which is also 1.33 x what this chip's experts
    receive in the 8-way deployment (8 chips' T k pairs over 8 chips).

    The two hidden products carry the name :data:`KEPT`: of a layer's 11
    matmuls in a step whose block is recomputed (3 forward, 6 backward, the
    two the backward reads made again) a policy that saves the name leaves
    9, for 4 T E f bytes. The identity under any other policy or none.
    Experts without a gate (``w_gate`` None) have ONE hidden product to
    name: 7 matmuls recomputed, 6 with the name, for 2 T E f bytes."""
    dt = tokens.dtype
    with scopes.scope(scopes.MOE_ROUTE):
        chose = expert[:, :, None] == jnp.asarray(held)[None, None, :]
        weight = jnp.sum(jnp.where(chose, gate[:, :, None], 0.0), axis=1)
        counts = jnp.sum(chose, axis=(0, 1), dtype=jnp.int32)     # [E]
    with scopes.scope(scopes.MOE_EXPERTS):
        product = lambda w: checkpoint_name(  # noqa: E731
            jnp.einsum("td,edf->tef", tokens, w.astype(dt)), KEPT)
        if w_gate is None:
            h = jnp.square(jax.nn.relu(product(w_up)))
        else:
            g, u = product(w_gate), product(w_up)
            h = activation(g) * u
        h = (h.astype(jnp.float32) * weight[:, :, None]).astype(dt)
        out = jnp.einsum("tef,efd->td", h, w_down.astype(dt),
                         preferred_element_type=jnp.float32)
    return out, counts
