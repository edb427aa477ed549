"""Fused w·V decompress + matvec Pallas kernel (paper Fig. 11, TPU-adapted).

Dot products run along the context dimension — the same direction V is
bit-packed — so each decoded [C_t, TL] tile contracts immediately against
the [G, TL] weight tile. The paper's fp32 ``atomicAdd`` partial sums become
sequential accumulation over the context tiles into the output block
(deterministic; grid dim 1 is "arbitrary" = sequential on TPU). Blocks as
in ``pallas_utils`` (a dense row or one paged page).
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


def _kernel(n_ref, payload_ref, mins_ref, shifts_ref, w_ref, out_ref, *,
            width, pack, tile_l, block_l):
    b, j = pl.program_id(0), pl.program_id(1)  # outside pl.when (interpret)
    n_live = n_ref[b]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    for s in range(block_l // tile_l):
        start = j * block_l + s * tile_l

        # tile skipping: a fully-masked tile accumulates exactly zero — skip
        # the decode and the dot_general (the init above still runs)
        @pl.when(start < n_live)
        def _accumulate(s=s, start=start):
            ws, ps, bs = tile_slices(s, tile_l, width, pack)
            vals = decode_tier_tile(payload_ref[:, ws], mins_ref[:, ps],
                                    shifts_ref[:, bs], width, pack)  # [C, TL]
            w = w_ref[:, s * tile_l : (s + 1) * tile_l]  # [G, TL]
            w = jnp.where(token_iota(start, tile_l) < n_live, w, 0.0)
            out_ref[...] += jax.lax.dot_general(
                w, vals, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )


def vpack_tier_out(
    payload: Array,
    mins: Array,
    shifts: Array,
    w: Array,
    *,
    width: int,
    pack_size: int,
    n_valid: Array | None = None,
    tile_l: int = DEFAULT_TILE_L,
) -> Array:
    """One tier's weighted-V output (tier channel order, scale pre-folded).

    payload: u32 [BH, C, L*width/32]; w: f32 [BH, G, L] (weights*scale).
    n_valid: optional i32 [BH] per-row valid length — weight columns past
    it are zeroed in-kernel before the contraction.
    Returns out f32 [BH, G, C].
    """
    BH, C, Wl = payload.shape
    G = w.shape[1]
    L = Wl * (32 // width)
    tile_l = min(tile_l, L)  # bucketed launches may slice below the tile
    assert L % tile_l == 0 and tile_l % (pack_size * 4) == 0
    nv = rows_valid(L if n_valid is None else n_valid, BH, 1)
    return packed_call(
        functools.partial(_kernel, width=width, pack=pack_size,
                          tile_l=tile_l, block_l=L),
        name="packkv_vpack_out",
        grid=(BH, 1),
        in_specs=[row_spec(payload.shape[1:]), row_spec(mins.shape[1:]),
                  row_spec(shifts.shape[1:]), row_block_spec(G, L)],
        out_specs=row_spec((G, C)),
        out_shape=jax.ShapeDtypeStruct((BH, G, C), jnp.float32),
        scalars=[nv],
        operands=[payload, mins, shifts, w],
        dimension_semantics=("parallel", "arbitrary"),
    )


def vpack_tier_out_paged(
    payload: Array,
    mins: Array,
    shifts: Array,
    w: Array,
    page_table: Array,
    n_valid: Array,
    *,
    width: int,
    pack_size: int,
    page_size: int,
    tile_l: int = DEFAULT_TILE_L,
) -> Array:
    """One tier's weighted-V output over a PAGED pool.

    payload/mins/shifts: pool layout [H_kv, n_pool_pages, C, ...];
    w: f32 [BH, G, n_tokens] dense bucket weights (scale pre-folded);
    page_table: i32 [B, max_pages]; n_valid: i32 [BH].
    Returns out f32 [BH, G, C] — bit-identical to ``vpack_tier_out`` on the
    gathered dense view.
    """
    h_kv = payload.shape[0]
    BH, G, n_tokens = w.shape
    C = payload.shape[2]
    tile_l = min(tile_l, page_size)
    assert page_size % tile_l == 0 and tile_l % (pack_size * 4) == 0
    assert n_tokens % page_size == 0 and n_tokens >= page_size
    n_pg = n_tokens // page_size
    page = lambda x: pool_page_spec(x, h_kv, n_pg, page_size)
    return packed_call(
        lambda n_ref, tab_ref, *refs: _kernel(
            n_ref, *refs, width=width, pack=pack_size, tile_l=tile_l,
            block_l=page_size),
        name="packkv_vpack_out_paged",
        grid=(BH, n_pg),
        in_specs=[page(payload), page(mins), page(shifts),
                  row_block_spec(G, page_size)],
        out_specs=row_spec((G, C)),
        out_shape=jax.ShapeDtypeStruct((BH, G, C), jnp.float32),
        scalars=[n_valid.astype(jnp.int32).reshape(BH),
                 page_table[:, :n_pg].reshape(-1).astype(jnp.int32)],
        operands=[payload, mins, shifts, w],
        dimension_semantics=("parallel", "arbitrary"),
    )
