"""Fused K decompress + q·Kᵀ Pallas kernel (paper Fig. 8, TPU-adapted).

One ``pallas_call`` per width tier covers ALL (batch × kv-head) rows and all
context tokens in a single launch — the TPU analogue of the paper's
single-kernel decompression (§III-B4): grid = (B·H_kv, n_blocks), blocks as
in ``pallas_utils`` (a dense row or one paged page).

Each compute tile decodes a [C_t, TL] integer tile from packed u32 words in
VMEM and contracts it with the [G, C_t] query slice on the MXU, producing
the [G, TL] integer-score tile. Per-token (scale, zero) are folded outside
as rank-1 corrections (see kernels/ref.py docstring) so decompressed data
never exists outside VMEM/VREGs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_utils import (
    packed_call,
    pool_page_spec,
    row_block_spec,
    row_spec,
    rows_valid,
    tile_slices,
    token_iota,
)
from .unpack import decode_tier_tile

Array = jax.Array

DEFAULT_TILE_L = 256


def _kernel(n_ref, payload_ref, mins_ref, shifts_ref, q_ref, out_ref, *,
            width, pack, tile_l, block_l):
    b, j = pl.program_id(0), pl.program_id(1)  # outside pl.when (interpret)
    n_live = n_ref[b]

    for s in range(block_l // tile_l):
        start = j * block_l + s * tile_l
        tok = slice(s * tile_l, (s + 1) * tile_l)

        def compute(s=s, start=start, tok=tok):
            ws, ps, bs = tile_slices(s, tile_l, width, pack)
            vals = decode_tier_tile(payload_ref[:, ws], mins_ref[:, ps],
                                    shifts_ref[:, bs], width, pack)  # [C, TL]
            out = jax.lax.dot_general(
                q_ref[...], vals, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            out_ref[:, tok] = jnp.where(token_iota(start, tile_l) < n_live,
                                        out, 0.0)

        def dead(tok=tok):
            out_ref[:, tok] = jnp.zeros((out_ref.shape[0], tile_l),
                                        out_ref.dtype)

        # tile skipping: a tile starting at/past this row's valid length is
        # all masked — write its (zero) output without decoding or the MXU
        live = start < n_live
        pl.when(live)(compute)
        pl.when(jnp.logical_not(live))(dead)


def kpack_tier_scores(
    payload: Array,
    mins: Array,
    shifts: Array,
    q: Array,
    *,
    width: int,
    pack_size: int,
    n_valid: Array | None = None,
    tile_l: int = DEFAULT_TILE_L,
) -> Array:
    """Integer score contribution of one tier.

    payload: u32 [BH, C, L*width/32]   mins: i8 [BH, C, L/pack]
    shifts:  u8  [BH, C, ceil(L/pack/4)]  q: f32 [BH, G, C] (tier channel slice)
    n_valid: optional i32 [BH] per-row valid length — score columns past it
    are zeroed in-kernel (per-slot batching: dead rows carry garbage packs).
    Returns si f32 [BH, G, L].
    """
    BH, C, Wl = payload.shape
    G = q.shape[1]
    L = Wl * (32 // width)
    tile_l = min(tile_l, L)  # bucketed launches may slice below the tile
    assert L % tile_l == 0 and tile_l % (pack_size * 4) == 0
    nv = rows_valid(L if n_valid is None else n_valid, BH, 1)
    return packed_call(
        functools.partial(_kernel, width=width, pack=pack_size,
                          tile_l=tile_l, block_l=L),
        name="packkv_kpack_scores",
        grid=(BH, 1),
        in_specs=[row_spec(payload.shape[1:]), row_spec(mins.shape[1:]),
                  row_spec(shifts.shape[1:]), row_spec((G, C))],
        out_specs=row_block_spec(G, L),
        out_shape=jax.ShapeDtypeStruct((BH, G, L), jnp.float32),
        scalars=[nv],
        operands=[payload, mins, shifts, q],
        dimension_semantics=("parallel", "parallel"),
    )


def kpack_tier_scores_paged(
    payload: Array,
    mins: Array,
    shifts: Array,
    q: Array,
    page_table: Array,
    n_valid: Array,
    n_tokens: int,
    *,
    width: int,
    pack_size: int,
    page_size: int,
    tile_l: int = DEFAULT_TILE_L,
) -> Array:
    """One tier's integer scores over a PAGED pool.

    payload: u32 [H_kv, n_pool_pages, C, page*width/32] (mins/shifts pool
    layout likewise); q: f32 [BH, G, C]; page_table: i32 [B, max_pages];
    n_valid: i32 [BH] per-row valid lengths (paged rows are always ragged);
    n_tokens: STATIC bucket (multiple of ``page_size``).
    Returns si f32 [BH, G, n_tokens] — bit-identical to ``kpack_tier_scores``
    on the gathered dense view.
    """
    h_kv = payload.shape[0]
    BH, G, C = q.shape
    tile_l = min(tile_l, page_size)
    assert page_size % tile_l == 0 and tile_l % (pack_size * 4) == 0
    assert n_tokens % page_size == 0 and n_tokens >= page_size
    n_pg = n_tokens // page_size
    page = lambda x: pool_page_spec(x, h_kv, n_pg, page_size)
    return packed_call(
        lambda n_ref, tab_ref, *refs: _kernel(
            n_ref, *refs, width=width, pack=pack_size, tile_l=tile_l,
            block_l=page_size),
        name="packkv_kpack_scores_paged",
        grid=(BH, n_pg),
        in_specs=[page(payload), page(mins), page(shifts), row_spec((G, C))],
        out_specs=row_block_spec(G, page_size),
        out_shape=jax.ShapeDtypeStruct((BH, G, n_tokens), jnp.float32),
        scalars=[n_valid.astype(jnp.int32).reshape(BH),
                 page_table[:, :n_pg].reshape(-1).astype(jnp.int32)],
        operands=[payload, mins, shifts, q],
        dimension_semantics=("parallel", "parallel"),
    )
