"""Shared Pallas plumbing: the platform's interpret/compile choice, TPU
compiler params, and the block specs of the dense and paged cache layouts.

Every packed kernel runs on a ``(B·H_kv, n_blocks)`` grid whose step
``(b, j)`` sees one BLOCK of ``block_l`` context tokens of row ``b``:

* dense layout — the block is the row's whole token axis (``n_blocks`` = 1);
* paged layout — the block is one physical page, resolved through the page
  table in the block index map (scalar prefetch), so only the addressed page
  is DMA'd into VMEM.

Either way the block's last two dims equal the array's (``[C_t, words]``),
which is what Mosaic requires of blocks narrower than 128 lanes: a 2-bit
tier's 256-token tile is only 16 words wide. Kernels then walk the block in
``tile_l``-token compute tiles with static lane slices (``tile_slices``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT_CAP = 96 * 2**20  # v5e has 128 MiB of VMEM per core
VMEM_HEADROOM = 16 * 2**20  # decoded tiles and spills beyond the blocks


def interpret_mode() -> bool:
    """The one place the platform picks how Pallas kernels run: in the
    interpreter on CPU, compiled by Mosaic on TPU."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"packed Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"the default backend is {platform!r}")


def _padded_bytes(shape, dtype) -> int:
    """VMEM bytes of one block: the last two dims pad to the (sublane,
    128-lane) tile, sublanes packing 32 bits (8 rows of f32, 32 of int8)."""
    itemsize = np.dtype(dtype).itemsize
    shape = tuple(d for d in shape if d is not None)
    rows, lanes = (1, *shape)[-2:]
    sub = 8 * 4 // itemsize
    rows = -(-rows // sub) * sub
    lanes = -(-lanes // 128) * 128
    return int(np.prod(shape[:-2], dtype=np.int64)) * rows * lanes * itemsize


def packed_call(kernel, *, name, grid, in_specs, out_specs, out_shape,
                scalars, operands, dimension_semantics):
    """``pallas_call`` of a packed kernel: ``scalars`` ride in SMEM as scalar
    prefetch (index maps and kernel body both read them), ``operands`` are
    blocked by ``in_specs``. The VMEM limit is the double-buffered blocks
    plus ``VMEM_HEADROOM``. ``name`` names the kernel's custom call in the
    compiled program, so a profile finds it by name."""
    listed = lambda x: list(x) if isinstance(x, (list, tuple)) else [x]
    blocks = sum(
        _padded_bytes(s.block_shape, x.dtype)
        for s, x in zip(in_specs + listed(out_specs),
                        list(operands) + listed(out_shape))
    )
    vmem = min(2 * blocks + VMEM_HEADROOM, VMEM_LIMIT_CAP)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=grid,
            in_specs=in_specs, out_specs=out_specs),
        out_shape=out_shape,
        interpret=interpret_mode(),
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=dimension_semantics, vmem_limit_bytes=vmem),
    )(*scalars, *operands)


def rows_valid(n, B: int, h_kv: int):
    """Scalar or per-row [B] valid-token counts -> i32 [B·H_kv], the
    kernels' batch-major row layout."""
    n = jnp.asarray(n, jnp.int32)
    n = n[None] if n.ndim == 0 else n
    return jnp.broadcast_to(n[:, None], (B, h_kv)).reshape(B * h_kv)


def row_spec(block_shape):
    """Dense layout: grid row ``b`` takes its whole ``[b, ...]`` slice."""
    zeros = (0,) * len(block_shape)
    return pl.BlockSpec((None, *block_shape), lambda b, j, *_: (b, *zeros))


def row_block_spec(rows: int, block_l: int):
    """``[BH, rows, n_tokens]`` operands (per-token metadata, weights,
    kpack scores): grid step ``(b, j)`` takes token block ``j`` of row ``b``."""
    return pl.BlockSpec((None, rows, block_l), lambda b, j, *_: (b, 0, j))


def pool_page_spec(leaf, h_kv: int, n_pg: int, page_size: int):
    """Paged layout: grid step ``(b, j)`` takes logical page ``j`` of its
    row, i.e. physical page ``table[row, j]`` of kv-head ``b % h_kv``
    (leaf ``[H_kv, n_pool_pages, C, units]``). Pages past the row's last
    live one re-address that page, so dead steps fetch nothing new."""

    def index(b, j, n_ref, tab_ref):
        last = jnp.maximum(n_ref[b] - 1, 0) // page_size
        return (b % h_kv, tab_ref[(b // h_kv) * n_pg + jnp.minimum(j, last)],
                0, 0)

    return pl.BlockSpec((None, None, *leaf.shape[2:]), index)


def tile_slices(s: int, tile_l: int, width: int, pack: int):
    """Static lane slices of compute tile ``s`` within a block, for the
    (payload, mins, shifts) leaves of a ``width``-bit tier. The offset
    triple (words / packs / shift bytes per tile) is THE token-axis layout
    contract (docs/formats.md)."""
    w, p, b = tile_l * width // 32, tile_l // pack, tile_l // pack // 4
    return slice(s * w, (s + 1) * w), slice(s * p, (s + 1) * p), \
        slice(s * b, (s + 1) * b)


def token_iota(start, tile_l: int, pieces: int = 1):
    """Global token indices ``[1, tile_l]`` of a tile's lanes (2-D: Mosaic
    has no 1-D iota). A tile in the field-major order of ``pieces`` k
    (``unpack.decode_tier_tile_fields``; ``tile_l`` a power of two) holds
    token ``j·k + f`` at lane ``f·m + j``, ``m = tile_l / k``; ``pieces``
    1 is the stored order."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile_l), 1)
    if pieces > 1:
        m = tile_l // pieces
        lane = (lane & (m - 1)) * pieces + (lane >> (m.bit_length() - 1))
    return start + lane


def field_major_lanes(x, pieces: int):
    """Rows ``[R, TL]`` of per-token values in the stored order, laid out
    in the field-major order of ``pieces`` (lane ``f·m + j`` takes token
    ``j·k + f``, as ``token_iota`` names them). In-kernel: lane gathers
    within each 128-lane vreg and a select per source vreg; Mosaic gathers
    only whole vregs of rows, so ``R`` > 1 (stack the rows to reorder)."""
    if pieces == 1:
        return x
    R, TL = x.shape
    src = jnp.broadcast_to(token_iota(0, TL, pieces), (R, TL))
    W = min(TL, 128)
    srcs = [x[:, d * W:(d + 1) * W] for d in range(TL // W)]
    out = []
    for c in range(TL // W):
        s = src[:, c * W:(c + 1) * W]
        y = None
        for d, xd in enumerate(srcs):
            g = jnp.take_along_axis(xd, s & (W - 1), axis=1)
            y = g if y is None else jnp.where(s // W == d, g, y)
        out.append(y)
    return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]
