"""Public jit'd wrappers over the computation-aware decompression kernels.

Backend dispatch:
  * ``"xla"``    — pure-jnp path (kernels/ref.py). XLA still fuses
    decode+matvec, and the cache bytes read from HBM are the compressed
    bytes, so the paper's bandwidth argument holds; this is the default on
    CPU and the path the production dry-run lowers.
  * ``"pallas"`` — explicit Pallas kernels (interpreted on CPU, compiled
    on TPU — ``pallas_utils.interpret_mode``): single-launch fused decode
    attention.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tiered import (
    TieredCache,
    chan_inverse_perm,
    gather_page_meta,
    page_prefix_ids,
)
from . import ref
from .kpack_matvec import kpack_tier_scores, kpack_tier_scores_paged
from .packed_attention import (
    DEFAULT_TILE_L,
    fused_packed_attention,
    fused_packed_attention_paged,
)
from .pallas_utils import rows_valid
from .vpack_matvec import vpack_tier_out, vpack_tier_out_paged

Array = jax.Array

NEG_INF = ref.NEG_INF


def _rows_to_bh(n: Array | None, B: int, h_kv: int) -> Array | None:
    """Broadcast scalar/[B] valid counts to the kernels' flat [BH] layout."""
    return None if n is None else rows_valid(n, B, h_kv)


def packed_qk_scores(
    q: Array,
    kc: TieredCache,
    sm_scale: float = 1.0,
    *,
    n_valid: Array | None = None,
    backend: str = "xla",
    tile_l: int = 256,
) -> Array:
    """q·Kᵀ over the compressed K cache. q: [B,H,D] -> scores [B,H,L].

    n_valid (scalar or per-row [B]): zero out scores of positions >= the
    row's valid length (callers still NEG_INF-mask before softmax; the
    zeroing keeps dead-slot garbage from propagating).
    """
    B, H, D = q.shape
    h_kv = kc.scale.shape[-2]
    if backend == "xla":
        s = ref.kpack_scores_ref(q, kc, sm_scale)
        if n_valid is not None:
            s = jnp.where(ref.valid_mask(n_valid, kc.capacity, lead=2), s, 0.0)
        return s
    G = H // h_kv
    BH = B * h_kv
    L = kc.capacity
    qg = q.astype(jnp.float32).reshape(B, h_kv, G, D)
    qp = jnp.take_along_axis(qg, kc.chan_perm[:, :, None, :], axis=-1)
    qf = qp.reshape(BH, G, D)
    flat = lambda a: a.reshape(BH, *a.shape[2:])
    nv = _rows_to_bh(n_valid, B, h_kv)
    si = jnp.zeros((BH, G, L), jnp.float32)
    off = 0
    for t, c in zip(kc.tiers, kc.spec.counts):
        si = si + kpack_tier_scores(
            flat(t.payload), flat(t.mins), flat(t.shifts), qf[..., off : off + c],
            n_valid=nv, width=t.width, pack_size=t.pack_size, tile_l=tile_l,
        )
        off += c
    qsum = jnp.sum(qf, axis=-1, keepdims=True)
    # si columns past each row's n_valid are already zeroed IN-KERNEL; only
    # the rank-1 zero-term correction still needs the outer mask
    zc = flat(kc.zero)[:, None, :]
    if nv is not None:
        zc = jnp.where(ref.valid_mask(nv, L, lead=2), zc, 0.0)
    scores = si * flat(kc.scale)[:, None, :] + qsum * zc
    return (scores * sm_scale).reshape(B, H, L)


def packed_weighted_v(
    w: Array,
    vc: TieredCache,
    *,
    n_valid: Array | None = None,
    backend: str = "xla",
    tile_l: int = 256,
) -> Array:
    """w·V over the compressed V cache. w: [B,H,L] -> out [B,H,D].

    n_valid (scalar or per-row [B]): positions >= the row's valid length
    contribute nothing — masked in-kernel on the pallas path, on the
    weights for the xla path (slot-table rows' tails hold recycled garbage).
    """
    B, H, L = w.shape
    h_kv = vc.scale.shape[-2]
    if backend == "xla":
        if n_valid is not None:
            w = jnp.where(ref.valid_mask(n_valid, L, lead=2), w, 0.0)
        return ref.vpack_out_ref(w, vc)
    G = H // h_kv
    BH = B * h_kv
    flat = lambda a: a.reshape(BH, *a.shape[2:])
    nv = _rows_to_bh(n_valid, B, h_kv)
    wf = w.astype(jnp.float32).reshape(BH, G, L)
    ws = wf * flat(vc.scale)[:, None, :]
    parts = [
        vpack_tier_out(
            flat(t.payload), flat(t.mins), flat(t.shifts), ws,
            n_valid=nv, width=t.width, pack_size=t.pack_size, tile_l=tile_l,
        )
        for t in vc.tiers
    ]
    out = jnp.concatenate(parts, axis=-1)  # [BH, G, Dv] tier order
    # zero-term correction runs outside the kernel -> mask its weights here
    if nv is not None:
        wf = jnp.where(ref.valid_mask(nv, L, lead=2), wf, 0.0)
    zterm = ref.einsum("bgl,bl->bg", wf, flat(vc.zero))[..., None]
    out = out + zterm
    out = out.reshape(B, h_kv, G, -1)
    inv = chan_inverse_perm(vc.chan_perm)
    out = jnp.take_along_axis(out, inv[:, :, None, :], axis=-1)
    return out.reshape(B, H, -1)


def packed_qk_scores_paged(
    q: Array,
    kc: TieredCache,
    pages,
    n_tokens: int,
    sm_scale: float = 1.0,
    *,
    n_valid: Array,
    backend: str = "xla",
    tile_l: int = 256,
) -> Array:
    """``packed_qk_scores`` over a PAGED K cache.

    kc: pool-layout TieredCache; pages: core.cache.PagePool; n_tokens:
    STATIC bucket (multiple of the page size). The xla backend gathers the
    live pages into the dense layout first; the pallas backend resolves
    each context tile's physical page inside the kernel. Returns scores
    f32 [B, H, n_tokens], bit-identical across the two routes.
    """
    B, H, D = q.shape
    h_kv = kc.scale.shape[0]
    if backend == "xla":
        from ..core.tiered import gather_tiered_pages

        idx = page_prefix_ids(pages.page_table, n_tokens, pages.page_size)
        return packed_qk_scores(
            q, gather_tiered_pages(kc, idx), sm_scale, n_valid=n_valid,
            backend="xla",
        )
    G = H // h_kv
    BH = B * h_kv
    qg = q.astype(jnp.float32).reshape(B, h_kv, G, D)
    qp = jnp.take_along_axis(qg, kc.chan_perm[:, :, None, :], axis=-1)
    qf = qp.reshape(BH, G, D)
    nv = _rows_to_bh(n_valid, B, h_kv)
    si = jnp.zeros((BH, G, n_tokens), jnp.float32)
    off = 0
    for t, c in zip(kc.tiers, kc.spec.counts):
        si = si + kpack_tier_scores_paged(
            t.payload, t.mins, t.shifts, qf[..., off : off + c],
            pages.page_table, nv, n_tokens, width=t.width,
            pack_size=t.pack_size, page_size=pages.page_size, tile_l=tile_l,
        )
        off += c
    qsum = jnp.sum(qf, axis=-1, keepdims=True)
    flatm = lambda a: gather_page_meta(
        a, pages.page_table, n_tokens, pages.page_size
    ).reshape(BH, n_tokens)
    zc = jnp.where(ref.valid_mask(nv, n_tokens, lead=2), flatm(kc.zero)[:, None, :], 0.0)
    scores = si * flatm(kc.scale)[:, None, :] + qsum * zc
    return (scores * sm_scale).reshape(B, H, n_tokens)


def packed_weighted_v_paged(
    w: Array,
    vc: TieredCache,
    pages,
    *,
    n_valid: Array,
    backend: str = "xla",
    tile_l: int = 256,
) -> Array:
    """``packed_weighted_v`` over a PAGED V cache.

    w: [B, H, n_tokens] dense bucket weights (n_tokens a STATIC multiple of
    the page size). Same backend split as ``packed_qk_scores_paged``.
    """
    B, H, n_tokens = w.shape
    h_kv = vc.scale.shape[0]
    if backend == "xla":
        from ..core.tiered import gather_tiered_pages

        idx = page_prefix_ids(pages.page_table, n_tokens, pages.page_size)
        return packed_weighted_v(
            w, gather_tiered_pages(vc, idx), n_valid=n_valid, backend="xla"
        )
    G = H // h_kv
    BH = B * h_kv
    nv = _rows_to_bh(n_valid, B, h_kv)
    flatm = lambda a: gather_page_meta(
        a, pages.page_table, n_tokens, pages.page_size
    ).reshape(BH, n_tokens)
    wf = w.astype(jnp.float32).reshape(BH, G, n_tokens)
    ws = wf * flatm(vc.scale)[:, None, :]
    parts = [
        vpack_tier_out_paged(
            t.payload, t.mins, t.shifts, ws, pages.page_table, nv,
            width=t.width, pack_size=t.pack_size, page_size=pages.page_size,
            tile_l=tile_l,
        )
        for t in vc.tiers
    ]
    out = jnp.concatenate(parts, axis=-1)  # [BH, G, Dv] tier order
    wf = jnp.where(ref.valid_mask(nv, n_tokens, lead=2), wf, 0.0)
    zterm = ref.einsum("bgl,bl->bg", wf, flatm(vc.zero))[..., None]
    out = out + zterm
    out = out.reshape(B, h_kv, G, -1)
    inv = chan_inverse_perm(vc.chan_perm)
    out = jnp.take_along_axis(out, inv[:, :, None, :], axis=-1)
    return out.reshape(B, H, -1)


def _residual_partials(q, resid_k, resid_v, n_resid, sm_scale):
    """LSE partials (o_unnorm, m, l) of attention over the residual buffer.

    n_resid: scalar or per-row [B] valid-token count.
    """
    B, H, D = q.shape
    h_kv = resid_k.shape[1]
    R = resid_k.shape[2]
    qg = q.astype(jnp.float32).reshape(B, h_kv, H // h_kv, D)
    s = ref.einsum("bhgd,bhrd->bhgr", qg, resid_k.astype(jnp.float32)) * sm_scale
    mask = ref.valid_mask(n_resid, R, lead=3)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = ref.einsum("bhgr,bhrd->bhgd", p, resid_v.astype(jnp.float32))
    return o.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)


def merge_partials(o1, m1, l1, o2, m2, l2):
    """Log-sum-exp merge of two unnormalized attention partials."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)[..., None]
    a2 = jnp.exp(m2 - m)[..., None]
    denom = l1[..., None] * a1 + l2[..., None] * a2
    return (o1 * a1 + o2 * a2) / jnp.maximum(denom, 1e-30)


def packed_decode_attention(
    q: Array,
    kc: TieredCache,
    vc: TieredCache,
    resid_k: Array,
    resid_v: Array,
    n_comp: Array,
    n_resid: Array,
    sm_scale: float,
    *,
    backend: str = "xla",
    tile_l: int = DEFAULT_TILE_L,
) -> Array:
    """Full decode attention over [compressed | residual] regions."""
    if backend == "xla":
        return ref.packed_decode_attention_ref(
            q, kc, vc, resid_k, resid_v, n_comp, n_resid, sm_scale
        )
    o_c, m_c, l_c = fused_packed_attention(
        q, kc, vc, n_comp, sm_scale, tile_l=tile_l
    )
    o_r, m_r, l_r = _residual_partials(q, resid_k, resid_v, n_resid, sm_scale)
    return merge_partials(o_c, m_c, l_c, o_r, m_r, l_r)


def paged_decode_attention(
    q: Array,
    cache,
    sm_scale: float,
    *,
    n_bucket: int | None = None,
    backend: str = "xla",
    tile_l: int = DEFAULT_TILE_L,
) -> Array:
    """Full decode attention over a PAGED compressed cache + residual.

    cache: a paged ``core.cache.LayerKVCache`` (compressed policy). The xla
    backend gathers the first ``n_bucket`` tokens' pages into the dense
    layout and runs the reference path; the pallas backend launches the
    page-indexed fused kernel directly on the pool. Both are bit-identical
    to ``packed_decode_attention`` on the dense storage mode.
    """
    n_tokens = cache.capacity if n_bucket is None else min(n_bucket, cache.capacity)
    if backend == "xla":
        from ..core.cache import gather_paged

        read = gather_paged(cache, n_tokens)
        return ref.packed_decode_attention_ref(
            q, read.k, read.v, read.resid_k, read.resid_v,
            read.n_comp, read.n_resid, sm_scale,
        )
    o_c, m_c, l_c = fused_packed_attention_paged(
        q, cache.k, cache.v, cache.pages.page_table, cache.n_comp, n_tokens,
        sm_scale, page_size=cache.cfg.page_size, tile_l=tile_l,
    )
    o_r, m_r, l_r = _residual_partials(
        q, cache.resid_k, cache.resid_v, cache.n_resid, sm_scale
    )
    return merge_partials(o_c, m_c, l_c, o_r, m_r, l_r)


dense_decode_attention = ref.dense_decode_attention_ref
