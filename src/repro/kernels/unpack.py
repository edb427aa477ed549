"""In-kernel decompression primitives shared by the Pallas kernels.

These run inside ``pl.pallas_call`` bodies: everything is static-shape,
uses only vectorizable integer ops (shift/mask/broadcast/reshape), and the
decoded values live purely in VMEM/VREGs — the TPU analogue of the paper's
"decompress into registers" (§III-C).

Two decodes of a tier tile:

* ``decode_tier_tile`` keeps the stored token order: every packed field is
  repeated out to token granularity along the lanes (``repeat_lanes``),
  then its bits are selected per lane from the token index. Mosaic lowers
  that one repeat pattern; it refuses the alternative of splitting words
  into a trailing axis of 4 shift fields and merging it back into the
  lanes. The repeats are lane shuffles, one per token, and cost far more
  than the arithmetic.
* ``decode_tier_tile_fields`` returns the tile in FIELD-MAJOR order
  (``pallas_utils.token_iota``): piece ``f`` is field ``f`` of every
  word, one whole-array shift and mask, and the pieces lie side by side.
  Only the per-pack mins and shift bits move between lanes, to their
  words, once per tile and on the MXU (a one-hot matmul, exact for these
  small integers). Attention over a tile is invariant to its token order,
  so the attention kernel uses this order when all its tiers share one
  width (docs/formats.md, "Attention kernel token order").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def repeat_lanes(x, r: int):
    """[C, n] -> [C, n * r], each column repeated ``r`` times."""
    C, n = x.shape
    return jnp.broadcast_to(x[:, :, None], (C, n, r)).reshape(C, n * r)


def _log2(n: int) -> int:
    assert n > 0 and n & (n - 1) == 0, f"{n} must be a power of two"
    return n.bit_length() - 1


def decode_tier_tile(payload, mins, shift_bytes, width: int, pack_size: int):
    """Decode one tier tile to integer values.

    payload:     u32 [C, TL*width/32]
    mins:        i8  [C, TL/pack_size]
    shift_bytes: u8  [C, TL/pack_size/4]
    Returns f32 [C, TL] decoded quantized integers (mid-rise reconstruction
    of shift-dropped low bits), ready for the integer matvec.
    """
    assert width >= 1 and 32 % width == 0
    vpw = 32 // width
    TL = payload.shape[1] * vpw
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, TL), 1)
    # token l: bits [(l % vpw)·w, +w) of word l // vpw
    bit = ((lane & (vpw - 1)) * width).astype(jnp.uint32)
    stored = ((repeat_lanes(payload, vpw) >> bit)
              & jnp.uint32(2**width - 1)).astype(jnp.int32)
    # pack p = l // pack: bits [2·(p % 4), +2) of shift byte p // 4
    field = ((lane >> _log2(pack_size)) & 3) * 2
    sh_t = (repeat_lanes(shift_bytes.astype(jnp.int32), 4 * pack_size)
            >> field) & 3
    mins_t = repeat_lanes(mins.astype(jnp.int32), pack_size)
    half = jnp.where(sh_t > 0, 1 << jnp.maximum(sh_t - 1, 0), 0)
    q = (stored << sh_t) + half + mins_t
    return q.astype(jnp.float32)


def _gather_lanes(x, idx):
    """``x[:, idx]`` for an i32 ``[C, a]`` of small integers (``|x| <=
    256``) and a computed ``[1, b]`` lane index: a one-hot matmul, exact in
    any precision (each output is one integer times 1.0), so the relayout
    runs on the MXU instead of the lane shufflers."""
    a, b = x.shape[1], idx.shape[1]
    onehot = jax.lax.broadcasted_iota(jnp.int32, (a, b), 0) == idx
    y = jax.lax.dot_general(x.astype(jnp.float32), onehot.astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y.astype(jnp.int32)


def decode_tier_tile_fields(payload, mins, shift_bytes, width: int,
                            pack_size: int):
    """``decode_tier_tile`` in field-major token order.

    Same operands and values; lane ``f·n + i`` of the result holds token
    ``i·k + f`` of the tile: field ``f`` of word ``i``, ``k = 32/width``,
    ``n`` words. Field ``f`` of every word is one whole-array shift and
    mask. Token ``i·k + f`` lies in pack ``(i·k + f) // pack_size``, so
    fields ``f`` with equal ``f // pack_size`` share one per-word pack
    index, and their mins and shift bits are gathered to words once.
    """
    assert width >= 1 and 32 % width == 0
    k = 32 // width
    n = payload.shape[1]
    word = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    groups = max(1, k // pack_size)  # packs in a word
    mins = mins.astype(jnp.int32)
    shift_bytes = shift_bytes.astype(jnp.int32)
    mask = jnp.uint32(2**width - 1)
    pieces = []
    for t in range(groups):
        pack = (word * k + t * pack_size) >> _log2(pack_size)
        mins_w = mins if k == pack_size else _gather_lanes(mins, pack)
        # pack p: bits [2·(p % 4), +2) of shift byte p // 4
        sh = (_gather_lanes(shift_bytes, pack >> 2) >> ((pack & 3) * 2)) & 3
        off = jnp.where(sh > 0, 1 << jnp.maximum(sh - 1, 0), 0) + mins_w
        for f in range(t * k // groups, (t + 1) * k // groups):
            stored = (payload >> jnp.uint32(f * width)) & mask
            pieces.append((stored.astype(jnp.int32) << sh) + off)
    return jnp.concatenate(pieces, axis=1).astype(jnp.float32)
