"""Single-launch fused decode attention over the compressed KV cache.

This is the paper's headline co-design (§III-C) adapted to TPU: ONE
``pallas_call`` decodes every K and V tier tile, computes the masked
softmax online (flash-style running max/sum), and accumulates the
weighted-V output — decompressed data and attention scores never leave
VMEM/VREGs; nothing is written back to HBM except the [G, D] output and
three [G] statistics used to merge with the full-precision residual
buffer via log-sum-exp (the deterministic TPU replacement for the paper's
fp32 ``atomicAdd`` partial sums).

Grid = (B·H_kv, n_blocks): grid dim 0 parallel over heads/batch, dim 1
sequential over context blocks, each walked in ``tile_l``-token compute
tiles (the flash recurrence). Inputs are generated programmatically from
the K/V tier specs, so any TierSpec combination lowers to a single kernel.

Two storage modes share one kernel body (``_attention_kernel``) and differ
only in their block specs (``pallas_utils``):

* ``fused_packed_attention`` — dense per-slot buffers; one block is the
  row's whole token axis.
* ``fused_packed_attention_paged`` — the compressed bytes live in a shared
  page pool; one block is one physical page, resolved through the slot's
  page table (scalar prefetch) in the block index map. Per-token
  scale/zero are gathered to the dense layout outside the kernel (rank-1
  metadata, bucket-sized). With equal ``tile_l`` both modes run the same
  tiles in the same order, so their outputs are bit-identical.

Token order within a tile: when every K and V tier has one width
(``launch_pieces`` > 1), tiles decode in field-major order
(``unpack.decode_tier_tile_fields``) and the kernel lays the tile's
per-token scale/zero out in that order (``pallas_utils.field_major_lanes``,
in-vreg lane gathers: reordering them outside would add a pass); the masked
softmax and the weighted V sum are taken over the tile's tokens, so only
the order of summation changes. Mixed widths keep the stored order and the
lane-repeat decode (``unpack.decode_tier_tile``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.tiered import TieredCache
from .pallas_utils import (
    field_major_lanes,
    packed_call,
    pool_page_spec,
    row_block_spec,
    row_spec,
    rows_valid,
    tile_slices,
    token_iota,
)
from .unpack import decode_tier_tile, decode_tier_tile_fields

Array = jax.Array

NEG_INF = -1e30
DEFAULT_TILE_L = 256
# the kernels' names in compiled programs (and so in a profile's ops)
DENSE_KERNEL = "packkv_attention"
PAGED_KERNEL = "packkv_paged_attention"


def _flash_tile_body(
    q,
    k_tiles,
    v_tiles,
    kscale_t,
    kzero_t,
    vscale_t,
    vzero_t,
    n_live,
    gidx,
    acc_ref,
    zsum_ref,
    m_ref,
    l_ref,
    *,
    k_offs,
    v_offs,
    sm_scale,
):
    """One context tile's flash update. ``k_tiles``/``v_tiles`` are the
    decoded integer tiles ([C_t, TL] f32 per tier); ``*_t`` are the tile's
    per-token metadata ([1, TL] f32); ``gidx`` the tile's global token
    indices [1, TL]; the running statistics are [G, 1]."""
    # ---- K: integer scores for this tile ----------------------------------
    si = None
    for t, vals in enumerate(k_tiles):
        qs = q[:, k_offs[t] : k_offs[t + 1]]  # [G, Ck_t]
        d = jax.lax.dot_general(
            qs, vals, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        si = d if si is None else si + d  # [G, TL]
    qsum = jnp.sum(q, axis=-1, keepdims=True)  # [G, 1]
    scores = (si * kscale_t + qsum * kzero_t) * sm_scale

    valid = (gidx < n_live).astype(jnp.float32)  # [1, TL]
    scores = jnp.where(valid > 0, scores, NEG_INF)

    # ---- online softmax ----------------------------------------------------
    m_prev = m_ref[...]  # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)  # [G, 1]
    p = jnp.exp(scores - m_new) * valid  # [G, TL]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    # ---- V: weighted accumulation ------------------------------------------
    ws = p * vscale_t  # fold per-token scale into weights
    acc_ref[...] *= alpha
    for t, vals in enumerate(v_tiles):
        d = jax.lax.dot_general(
            ws, vals, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # [G, Cv_t]
        acc_ref[:, v_offs[t] : v_offs[t + 1]] += d
    zsum_ref[...] = zsum_ref[...] * alpha + jnp.sum(
        p * vzero_t, axis=-1, keepdims=True)


def _attention_kernel(
    n_ref,
    *refs,
    nk: int,
    nv: int,
    k_widths,
    v_widths,
    k_offs,
    v_offs,
    pack: int,
    pieces: int,
    sm_scale: float,
    tile_l: int,
    block_l: int,
):
    """refs layout: [k_payload*nk, k_mins*nk, k_shifts*nk, kscale, kzero,
    v_payload*nv, v_mins*nv, v_shifts*nv, vscale, vzero, q,
    acc_out, zsum_out, m_out, l_out]; ``n_ref`` holds every row's
    valid-token count (scalar prefetch). ``pieces`` > 1: tiles decode in
    field-major order (``launch_pieces``) and their metadata is reordered
    to match."""
    decode = decode_tier_tile_fields if pieces > 1 else decode_tier_tile
    i = 0
    k_pay = refs[i : i + nk]; i += nk
    k_min = refs[i : i + nk]; i += nk
    k_shf = refs[i : i + nk]; i += nk
    kscale_ref, kzero_ref = refs[i], refs[i + 1]; i += 2
    v_pay = refs[i : i + nv]; i += nv
    v_min = refs[i : i + nv]; i += nv
    v_shf = refs[i : i + nv]; i += nv
    vscale_ref, vzero_ref = refs[i], refs[i + 1]; i += 2
    q_ref = refs[i]; i += 1
    acc_ref, zsum_ref, m_ref, l_ref = refs[i : i + 4]

    b, j = pl.program_id(0), pl.program_id(1)  # outside pl.when (interpret)
    n_live = n_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        zsum_ref[...] = jnp.zeros_like(zsum_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tier_tiles(pays, mins, shfs, widths, s):
        tiles = []
        for t in range(len(pays)):
            ws, ps, bs = tile_slices(s, tile_l, widths[t], pack)
            tiles.append(decode(pays[t][:, ws], mins[t][:, ps],
                                shfs[t][:, bs], widths[t], pack))
        return tiles

    for s in range(block_l // tile_l):
        start = j * block_l + s * tile_l
        tok = slice(s * tile_l, (s + 1) * tile_l)

        # tile skipping: a tile starting at/past this row's valid length is
        # a flash no-op (scores all NEG_INF -> m_new = m_prev, alpha = 1,
        # p = 0), so skip the K/V decode, both dot_generals and the update
        @pl.when(start < n_live)
        def _live_tile(s=s, start=start, tok=tok):
            meta = [r[:, tok] for r in (kscale_ref, kzero_ref, vscale_ref,
                                        vzero_ref)]
            if pieces > 1:  # in the tile's token order, as one [4, TL]
                meta = field_major_lanes(jnp.concatenate(meta, axis=0),
                                         pieces)
                meta = [meta[r : r + 1] for r in range(4)]
            _flash_tile_body(
                q_ref[...],
                tier_tiles(k_pay, k_min, k_shf, k_widths, s),
                tier_tiles(v_pay, v_min, v_shf, v_widths, s),
                *meta,
                n_live, token_iota(start, tile_l, pieces),
                acc_ref, zsum_ref, m_ref, l_ref,
                k_offs=k_offs, v_offs=v_offs, sm_scale=sm_scale,
            )


def launch_pieces(k_spec, v_spec, tile_l: int) -> int:
    """Pieces of the token order the attention kernel decodes ``tile_l``
    tiles in for these K and V ``TierSpec``s: the field-major order of
    their one width when every tier of both has it and ``tile_l`` is a
    power of two (a paged launch's always is), else 1 (the stored order)."""
    widths = {*k_spec.widths, *v_spec.widths}
    if len(widths) != 1 or 0 in widths or tile_l & (tile_l - 1):
        return 1
    return 32 // widths.pop()  # the fields of a word


def paged_pieces(k_spec, v_spec, page_size: int,
                 tile_l: int = DEFAULT_TILE_L) -> int:
    """``launch_pieces`` of a paged launch, whose compute tile is ``tile_l``
    cut to the page (``fused_packed_attention_paged``)."""
    return launch_pieces(k_spec, v_spec, min(tile_l, page_size))


def _offsets(counts) -> tuple[int, ...]:
    return (0, *[sum(counts[: i + 1]) for i in range(len(counts))])


def paged_grid(batch: int, h_kv: int, n_tokens: int,
               page_size: int) -> tuple[int, int]:
    """The paged kernel's grid: one row per (batch row, kv head), one step
    per logical page of the ``n_tokens`` launch bucket."""
    assert n_tokens % page_size == 0, (n_tokens, page_size)
    return batch * h_kv, n_tokens // page_size


def paged_grid_steps(n_comp, h_kv: int, n_tokens: int,
                     page_size: int) -> tuple[int, int]:
    """(grid steps, dead steps) of one paged launch whose rows hold
    ``n_comp`` compressed tokens each (host ints, 0 for a free row). A step
    is dead when its page lies past its row's last live page: the index map
    re-addresses that page and every tile of the step is skipped, but the
    step still runs."""
    rows, n_pg = paged_grid(len(n_comp), h_kv, n_tokens, page_size)
    live = h_kv * sum(min(-(-int(n) // page_size), n_pg) for n in n_comp)
    return rows * n_pg, rows * n_pg - live


def _launch(q, kc, vc, n_comp, sm_scale, *, name, tile_l, pieces, block_l,
            grid, leaf_spec, operands, metas, table=None):
    """Shared launch of ``_attention_kernel``: ``pieces`` is the tiles'
    token order (``launch_pieces``), ``leaf_spec`` blocks a tier leaf,
    ``operands(tc)`` lists a cache's (payload, mins, shifts) leaves,
    ``metas`` are the [BH, 1, n_tokens] (kscale, kzero, vscale, vzero);
    ``table`` is the flat page table of a paged launch."""
    from ..core.tiered import chan_inverse_perm

    B, H, D = q.shape
    h_kv = kc.chan_perm.shape[-2]
    G = H // h_kv
    BH = B * h_kv
    assert grid[0] == BH, (grid, BH)
    pack = kc.spec.pack_size
    Dv = vc.spec.head_dim
    assert block_l % tile_l == 0 and tile_l % (pack * 4) == 0, (block_l, tile_l)

    # absorb the K channel permutation into q (free — paper §III-B3)
    qg = q.astype(jnp.float32).reshape(B, h_kv, G, D)
    qf = jnp.take_along_axis(qg, kc.chan_perm[:, :, None, :], axis=-1)
    qf = qf.reshape(BH, G, D)

    k_leaves, v_leaves = operands(kc), operands(vc)
    meta_spec = row_block_spec(1, block_l)
    in_specs = (
        [leaf_spec(x) for x in k_leaves] + [meta_spec, meta_spec]
        + [leaf_spec(x) for x in v_leaves] + [meta_spec, meta_spec]
        + [row_spec((G, D))]
    )
    out_specs = [row_spec((G, Dv)), row_spec((G, 1)), row_spec((G, 1)),
                 row_spec((G, 1))]
    out_shape = [
        jax.ShapeDtypeStruct((BH, G, Dv), jnp.float32),
        jax.ShapeDtypeStruct((BH, G, 1), jnp.float32),
        jax.ShapeDtypeStruct((BH, G, 1), jnp.float32),
        jax.ShapeDtypeStruct((BH, G, 1), jnp.float32),
    ]
    kernel = functools.partial(
        _attention_kernel,
        nk=len(kc.tiers),
        nv=len(vc.tiers),
        k_widths=tuple(t.width for t in kc.tiers),
        v_widths=tuple(t.width for t in vc.tiers),
        k_offs=_offsets(kc.spec.counts),
        v_offs=_offsets(vc.spec.counts),
        pack=pack,
        pieces=pieces,
        sm_scale=sm_scale,
        tile_l=tile_l,
        block_l=block_l,
    )
    scalars = [rows_valid(n_comp, B, h_kv)]
    if table is not None:  # the page table is read by the index maps only
        body = kernel
        kernel = lambda n_ref, tab_ref, *refs: body(n_ref, *refs)
        scalars.append(table)
    kscale, kzero, vscale, vzero = metas
    acc, zsum, m, lsum = packed_call(
        kernel,
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scalars=scalars,
        operands=[*k_leaves, kscale, kzero, *v_leaves, vscale, vzero, qf],
        dimension_semantics=("parallel", "arbitrary"),
    )

    o = acc + zsum  # zero-term correction (all channels)
    o = o.reshape(B, h_kv, G, Dv)
    inv = chan_inverse_perm(vc.chan_perm)
    o = jnp.take_along_axis(o, inv[:, :, None, :], axis=-1)
    return (
        o.reshape(B, H, Dv),
        m.reshape(B, H),
        lsum.reshape(B, H),
    )


def _tier_leaves(tc: TieredCache):
    return ([t.payload for t in tc.tiers] + [t.mins for t in tc.tiers]
            + [t.shifts for t in tc.tiers])


def fused_packed_attention(
    q: Array,
    kc: TieredCache,
    vc: TieredCache,
    n_comp: Array,
    sm_scale: float,
    *,
    tile_l: int = DEFAULT_TILE_L,
):
    """Compressed-region attention partials in ONE kernel launch.

    q: f32 [B, H, D] in ORIGINAL channel order. n_comp: scalar or per-row
    [B] valid lengths (continuous batching — each grid row masks to its own
    count). Returns (o_unnorm [B,H,Dv] in original channel order, m [B,H],
    l [B,H]) — log-sum-exp partials for merging with the residual buffer.
    """
    B = q.shape[0]
    h_kv = kc.scale.shape[-2]
    L = kc.capacity
    # bucketed launches can slice the cache below the default tile; clamp so
    # a small live prefix lowers as a single (smaller) tile
    tile_l = min(tile_l, L)
    flat = lambda a: a.reshape(B * h_kv, *a.shape[2:])
    meta = lambda a: a.reshape(B * h_kv, 1, L)
    return _launch(
        q, kc, vc, n_comp, sm_scale, name=DENSE_KERNEL, tile_l=tile_l,
        pieces=launch_pieces(kc.spec, vc.spec, tile_l), block_l=L, grid=(B * h_kv, 1),
        leaf_spec=lambda x: row_spec(x.shape[1:]),
        operands=lambda tc: [flat(x) for x in _tier_leaves(tc)],
        metas=[meta(kc.scale), meta(kc.zero), meta(vc.scale), meta(vc.zero)],
    )


def fused_packed_attention_paged(
    q: Array,
    kc: TieredCache,
    vc: TieredCache,
    page_table: Array,
    n_comp: Array,
    n_tokens: int,
    sm_scale: float,
    *,
    page_size: int,
    tile_l: int = DEFAULT_TILE_L,
):
    """Compressed-region attention partials over a PAGED cache in one launch.

    kc/vc: pool-layout TieredCaches (leaves [H_kv, n_pool_pages, ...]);
    page_table: i32 [B, max_pages]; n_tokens: STATIC bucket size (multiple
    of ``page_size``) — the grid covers ``n_tokens / page_size`` logical
    pages and each step's block is the physical page the table names.
    Returns the same (o_unnorm, m, l) partials as ``fused_packed_attention``
    and is bit-identical to running it on the gathered dense view.
    """
    from ..core.tiered import gather_page_meta

    B = q.shape[0]
    h_kv = kc.scale.shape[0]
    pieces = paged_pieces(kc.spec, vc.spec, page_size, tile_l)
    tile_l = min(tile_l, page_size)
    grid = paged_grid(B, h_kv, n_tokens, page_size)
    n_pg = grid[1]
    # per-token metadata is rank-1 and bucket-sized: gather it dense outside
    meta = lambda a: gather_page_meta(
        a, page_table, n_tokens, page_size).reshape(B * h_kv, 1, n_tokens)
    return _launch(
        q, kc, vc, n_comp, sm_scale, name=PAGED_KERNEL, tile_l=tile_l,
        pieces=pieces, block_l=page_size, grid=grid,
        leaf_spec=lambda x: pool_page_spec(x, h_kv, n_pg, page_size),
        operands=_tier_leaves,
        metas=[meta(kc.scale), meta(kc.zero), meta(vc.scale), meta(vc.zero)],
        table=page_table[:, :n_pg].reshape(-1).astype(jnp.int32),
    )
