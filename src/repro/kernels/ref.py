"""Pure-jnp oracles for the computation-aware decompression kernels.

These implement exactly the math the Pallas kernels must reproduce
(paper §III-C adapted to the TPU tiered format, DESIGN.md §3):

* ``kpack_scores_ref``   — fused K decompress + q·Kᵀ (paper Fig. 8).
* ``vpack_out_ref``      — fused w·V decompress + matvec (paper Fig. 11).
* ``packed_decode_attention_ref`` — the full single-launch decode attention
  over the compressed region + residual buffer, merged flash-style
  (replaces the paper's atomicAdd partial sums with a log-sum-exp merge).

Metadata folding (the TPU analogue of the paper's "decompress into
registers"): token-wise dequantization is never materialized. With
K_deq[l, c] = q_int[l, c] * scale[l] + zero[l],

  scores[l] = scale[l] * (q · q_int[:, l]) + zero[l] * sum_c(q[c])
  out[c]    = sum_l (w[l] * scale[l]) * q_int[c, l]  +  sum_l w[l] * zero[l]

so the integer matvec runs directly on decoded integers and the per-token
(scale, zero) are folded in as rank-1 corrections.

These oracles consume the DENSE TieredCache layout only. Paged caches
reach them through the page-table gather (``core.cache.gather_paged`` /
``tiered.gather_tiered_pages``), which reassembles the dense layout
bit-identically — so one oracle covers both storage modes, and the paged
Pallas kernels are checked against the gathered dense launch
(tests/test_paged.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tiered import TieredCache, chan_inverse_perm, unpack_tier

Array = jax.Array

NEG_INF = -1e30


def einsum(spec: str, *operands: Array) -> Array:
    """``jnp.einsum`` at f32 precision. On TPU, XLA's default would round
    f32 operands to bf16 for one MXU pass; the Pallas kernels contract in
    f32, and the oracle must compute what they compute."""
    return jnp.einsum(spec, *operands, precision=jax.lax.Precision.HIGHEST)


def valid_mask(n: Array, length: int, lead: int) -> Array:
    """``arange(length) < n`` with ``lead`` broadcast axes before the length.

    ``n`` is a valid-token count: scalar (uniform wave) or [B] (per-row
    slot state). Returns [1]*lead + [length] for a scalar, or
    [B] + [1]*(lead-1) + [length] for a vector — broadcastable against
    [B, ..., length] score tensors either way.
    """
    n = jnp.asarray(n)
    ar = jnp.arange(length)
    if n.ndim == 0:
        return (ar < n).reshape((1,) * lead + (length,))
    return ar.reshape((1,) * lead + (length,)) < n.reshape((-1,) + (1,) * lead)


def _grouped_q(q: Array, h_kv: int) -> Array:
    """[B, H, D] -> [B, H_kv, G, D] (GQA grouping)."""
    B, H, D = q.shape
    return q.reshape(B, h_kv, H // h_kv, D)


def kpack_scores_ref(q: Array, kc: TieredCache, sm_scale: float = 1.0) -> Array:
    """Fused K decompress + q·Kᵀ.

    q:  f32 [B, H, D] query in ORIGINAL channel order.
    kc: compressed K, channels-major tier layout, capacity L.
    Returns scores f32 [B, H, L] (no masking — caller masks to n_valid).
    """
    B, H, D = q.shape
    h_kv = kc.scale.shape[-2]
    L = kc.capacity
    qg = _grouped_q(q.astype(jnp.float32), h_kv)  # [B, Hkv, G, D]
    # channel permutation of K is absorbed by permuting q (free).
    qp = jnp.take_along_axis(qg, kc.chan_perm[:, :, None, :], axis=-1)
    # integer matvec per tier
    si = jnp.zeros((B, h_kv, qg.shape[2], L), jnp.float32)
    off = 0
    for t, c in zip(kc.tiers, kc.spec.counts):
        qint = unpack_tier(t, L).astype(jnp.float32)  # [B, Hkv, C_t, L]
        si = si + einsum("bhgc,bhcl->bhgl", qp[..., off : off + c], qint)
        off += c
    qsum = jnp.sum(qg, axis=-1, keepdims=True)  # [B, Hkv, G, 1]
    scores = si * kc.scale[:, :, None, :] + qsum * kc.zero[:, :, None, :]
    return (scores * sm_scale).reshape(B, H, L)


def vpack_out_ref(w: Array, vc: TieredCache) -> Array:
    """Fused w·V decompress + matvec.

    w:  f32 [B, H, L] attention weights (already softmaxed & masked).
    vc: compressed V. Returns out f32 [B, H, D] in ORIGINAL channel order.
    """
    B, H, L = w.shape
    h_kv = vc.scale.shape[-2]
    wg = w.astype(jnp.float32).reshape(B, h_kv, H // h_kv, L)
    ws = wg * vc.scale[:, :, None, :]  # fold scale into weights
    parts = []
    for t in vc.tiers:
        qint = unpack_tier(t, L).astype(jnp.float32)  # [B, Hkv, C_t, L]
        parts.append(einsum("bhgl,bhcl->bhgc", ws, qint))
    out = jnp.concatenate(parts, axis=-1)  # tier channel order
    zterm = einsum("bhgl,bhl->bhg", wg, vc.zero)[..., None]
    out = out + zterm
    inv = chan_inverse_perm(vc.chan_perm)  # undo channel permutation
    out = jnp.take_along_axis(out, inv[:, :, None, :], axis=-1)
    return out.reshape(B, H, -1)


def packed_decode_attention_ref(
    q: Array,
    kc: TieredCache,
    vc: TieredCache,
    resid_k: Array,
    resid_v: Array,
    n_comp: Array,
    n_resid: Array,
    sm_scale: float,
) -> Array:
    """Full decode attention: softmax over [compressed | residual] regions.

    q: [B, H, D]; resid_k/v: [B, H_kv, R, D] full precision.
    n_comp/n_resid: scalar or per-row [B] valid-token counts.
    Returns attention output [B, H, D].
    """
    B, H, D = q.shape
    h_kv = resid_k.shape[1]
    L = kc.capacity
    R = resid_k.shape[2]

    s_comp = kpack_scores_ref(q, kc, sm_scale)  # [B, H, L]
    mask_c = valid_mask(n_comp, L, lead=2)
    s_comp = jnp.where(mask_c, s_comp, NEG_INF)

    qg = _grouped_q(q.astype(jnp.float32), h_kv)
    s_res = einsum(
        "bhgd,bhrd->bhgr", qg, resid_k.astype(jnp.float32)
    ).reshape(B, H, R) * sm_scale
    mask_r = valid_mask(n_resid, R, lead=2)
    s_res = jnp.where(mask_r, s_res, NEG_INF)

    m = jnp.maximum(jnp.max(s_comp, -1, keepdims=True), jnp.max(s_res, -1, keepdims=True))
    w_comp = jnp.exp(s_comp - m)
    w_res = jnp.exp(s_res - m)
    # zero out masked lanes exactly (exp(NEG_INF - m) underflows anyway)
    w_comp = jnp.where(mask_c, w_comp, 0.0)
    w_res = jnp.where(mask_r, w_res, 0.0)
    denom = jnp.sum(w_comp, -1, keepdims=True) + jnp.sum(w_res, -1, keepdims=True)

    o_comp = vpack_out_ref(w_comp, vc)  # [B, H, D] (unnormalized)
    wg = w_res.reshape(B, h_kv, H // h_kv, R)
    o_res = einsum("bhgr,bhrd->bhgd", wg, resid_v.astype(jnp.float32)).reshape(B, H, D)
    return (o_comp + o_res) / jnp.maximum(denom, 1e-30)


def dense_decode_attention_ref(
    q: Array,
    raw_k: Array,
    raw_v: Array,
    resid_k: Array,
    resid_v: Array,
    n_comp: Array,
    n_resid: Array,
    sm_scale: float,
) -> Array:
    """Uncompressed-cache decode attention (the cuBLAS-equivalent baseline).

    raw_k/v: [B, H_kv, L, D] bf16. n_comp/n_resid: scalar or per-row [B].
    """
    B, H, D = q.shape
    h_kv = raw_k.shape[1]
    L, R = raw_k.shape[2], resid_k.shape[2]
    qg = _grouped_q(q.astype(jnp.float32), h_kv)
    s_c = einsum("bhgd,bhld->bhgl", qg, raw_k.astype(jnp.float32)) * sm_scale
    s_r = einsum("bhgd,bhrd->bhgr", qg, resid_k.astype(jnp.float32)) * sm_scale
    mask_c = valid_mask(n_comp, L, lead=3)
    mask_r = valid_mask(n_resid, R, lead=3)
    s_c = jnp.where(mask_c, s_c, NEG_INF)
    s_r = jnp.where(mask_r, s_r, NEG_INF)
    m = jnp.maximum(s_c.max(-1, keepdims=True), s_r.max(-1, keepdims=True))
    w_c = jnp.where(mask_c, jnp.exp(s_c - m), 0.0)
    w_r = jnp.where(mask_r, jnp.exp(s_r - m), 0.0)
    denom = w_c.sum(-1, keepdims=True) + w_r.sum(-1, keepdims=True)
    o = einsum("bhgl,bhld->bhgd", w_c, raw_v.astype(jnp.float32)) + einsum(
        "bhgr,bhrd->bhgd", w_r, resid_v.astype(jnp.float32)
    )
    return (o / jnp.maximum(denom, 1e-30)).reshape(B, H, D)
