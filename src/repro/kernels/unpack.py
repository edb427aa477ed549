"""In-kernel decompression primitives shared by the Pallas kernels.

These run inside ``pl.pallas_call`` bodies: everything is static-shape,
uses only vectorizable integer ops (shift/mask/broadcast/reshape), and the
decoded values live purely in VMEM/VREGs — the TPU analogue of the paper's
"decompress into registers" (§III-C).

Every packed field is first repeated out to token granularity along the
lanes (``repeat_lanes``), then its bits are selected per lane from the
token index. Mosaic lowers that one repeat pattern; it refuses the
alternative of splitting words into a trailing axis of 4 shift fields and
merging it back into the lanes.
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
