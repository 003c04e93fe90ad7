"""Mamba-2's selective state-space recurrence in its chunked dual form
(state-space duality, arXiv 2405.21060), plain ``jnp`` / ``lax`` and
differentiated by JAX.

Per head h with a scalar decay (P = head_dim features, N = state size; the
heads share B and C in groups of ``H / G``), in float32:

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        S in R^{P x N}, S_0 = 0
    y_t = S_t C_t + D x_t                     a_t = exp(dt_t A), A < 0

Token by token that is S sequential steps of rank-one updates. The dual
form cuts the sequence into chunks of ``chunk`` tokens and turns all but
the carry between chunks into products (:func:`ssd_chunked`):

- inside a chunk ``Y_intra = (L o C B^T) (dt x)`` with the masked decay
  ``L_ts = exp(sum_{s < r <= t} dt_r A)`` for s <= t, 0 above the
  diagonal: the cumulative sums' DIFFERENCES, masked BEFORE the ``exp``
  (never a ratio of two exponentials, which overflows where a chunk decays
  far), and ``C B^T`` made once a GROUP;
- a chunk's own state ``sum_s exp(sum_{r > s} dt_r A) dt_s x_s B_s^T``;
- the states carried from chunk to chunk by ``lax.scan`` in float32,
  ``S_c = exp(sum_chunk dt A) S_{c-1} + (chunk c's own)``;
- ``Y_inter = exp(sum_{r <= t} dt_r A) C_t S_prev`` from the state that
  ENTERS the chunk.

The products take operands of ``dtype`` (the model's: bfloat16 on the
chip) and accumulate in float32; decays, cumulative sums and the carried
states are float32 whatever ``dtype``. A sequence that is not whole chunks
is padded here with ``dt = 0`` rows, which neither decay nor write.

No kernel and no kept name: a block recomputed in the backward pass runs
this twice. Whether either pays is a question for the readings of
``ssd_scan_ms_per_step`` and ``remat_ms_per_step``. No state is reset
inside a sequence (a packed document's boundary is not known here).
"""
import jax
import jax.numpy as jnp


def ssd_chunked(x, dt, a, b, c, d, chunk: int, dtype=jnp.float32):
    """``x`` [B, S, H, P], ``dt`` [B, S, H] float32 (positive: after its
    softplus), ``a`` [H] float32 (negative), ``b`` / ``c`` [B, S, G, N]
    with H a multiple of G (head h reads group ``h // (H / G)``), ``d``
    [H] -> (y [B, S, H, P] in ``dtype``, the mean over batch, chunks and
    heads of ``exp(sum_chunk dt A)``: what of a chunk's incoming state
    survives the chunk)."""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    if H % G:
        raise ValueError("%d heads do not share %d groups of B and C"
                         % (H, G))
    K = H // G
    pad = -S % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    n = (S + pad) // chunk
    f32 = jnp.float32

    def chunks(t, *head):
        """[B, S, heads.., f] -> [B, n, heads.., L, f]: a chunk's tokens
        beside its features, the two minor axes of every product."""
        t = t.reshape((B, n, chunk) + head + t.shape[t.ndim - 1:])
        return jnp.moveaxis(t, 2, -2)

    # a head is (its group, its place in the group)
    xc = chunks(x, G, K)                                     # [B,n,G,K,L,P]
    dtc = chunks(dt.astype(f32)[..., None], G, K)[..., 0]    # [B,n,G,K,L]
    bc, cc = (chunks(t.astype(dtype), G) for t in (b, c))    # [B,n,G,L,N]
    cum = jnp.cumsum(dtc * a.astype(f32).reshape(G, K, 1), axis=-1)
    total = cum[..., -1]                                     # [B,n,G,K]
    xdt32 = xc.astype(f32) * dtc[..., None]
    xdt = xdt32.astype(dtype)

    # inside a chunk: (L o C B^T)(dt x), L masked before its exp
    decay = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((chunk, chunk), bool)),
        cum[..., :, None] - cum[..., None, :], -jnp.inf))    # [B,n,G,K,L,L]
    cb = jnp.einsum("bngts,bngus->bngtu", cc, bc,
                    preferred_element_type=f32)              # once a group
    y = jnp.einsum("bngktu,bngkup->bngktp",
                   (cb[:, :, :, None] * decay).astype(dtype), xdt,
                   preferred_element_type=f32)

    # a chunk's own state, and the states carried from chunk to chunk
    to_end = jnp.exp(total[..., None] - cum)                 # [B,n,G,K,L]
    own = jnp.einsum("bngkup,bngus->bngkps",
                     (xdt32 * to_end[..., None]).astype(dtype), bc,
                     preferred_element_type=f32)             # [B,n,G,K,P,N]

    def carry(state, chunk_c):
        own_c, total_c = chunk_c
        return jnp.exp(total_c)[..., None, None] * state + own_c, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((B, G, K, P, N), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                  # [B,n,G,K,P,N]
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bngts,bngkps->bngktp", cc, entering.astype(dtype),
        preferred_element_type=f32)
    y = y + d.astype(f32).reshape(G, K, 1, 1) * xc.astype(f32)
    y = jnp.moveaxis(y, -2, 2).reshape(B, S + pad, H, P)[:, :S]
    return y.astype(dtype), jnp.mean(jnp.exp(total))
