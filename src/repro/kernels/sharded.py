"""KV-head sharding lanes for the multi-device serving engine.

The engine shards the compressed pool PAYLOADS (K/V tier bytes, tier
scales/zeros, residual buffers, calibration perms) over a ``kv`` mesh
axis by KV head — the head-major pool layout ``[H_kv, pool_pages, ...]``
makes the head axis the natural partition — while the page LEDGER (page
table, free list, refcounts) and the per-row counters stay replicated,
so the host scheduler's reservation arithmetic reads one
device-identical source of truth (docs/architecture.md).

Every cache-touching jitted dispatch runs inside a shard_map "lane"
(``sharded_call``): the unmodified model code asks ``active_lane()``
whether it is on a head shard, slices its contiguous q/k/v head block
(GQA query heads group contiguously by KV head — kernels/ref
``_grouped_q`` — so one slice serves q, k and v), runs the ordinary
attention + cache-append math on local heads, and merges attention
outputs back with ONE ``psum`` of disjoint scatters per layer. Because
every per-head computation is head-independent (softmax, tier matvecs,
quantization, calibration are all per-(row, head)), and the merge adds
each output cell as ``x + 0 + ... + 0``, the sharded result is
BIT-IDENTICAL to the single-device run — no reduction-order change
anywhere.

Data-parallel slot sharding composes over a ``dp`` axis: cache STATE
stays replicated across ``dp`` (every shard runs the identical append),
and only the attention READ is partitioned — a shard masks the per-row
counters to its owned rows (a masked row spans zero context tokens and
every decode kernel guards its softmax denominator, so it contributes
exact ``0.0``), and the same disjoint-scatter psum assembles the row
blocks. Batches that don't divide ``dp`` degrade to fully-replicated
compute, still exact.

This module replaced the seed-era context-parallel decode prototype
(``context_parallel_decode_step``): head sharding needs no cross-shard
log-sum-exp merge at all — each shard owns complete softmax rows — so
there is now one sharded decode path, the lane, shared by decode,
verify, prefill-insert and the chunked/prefix admission segments.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

Array = jax.Array

KV_AXIS = "kv"
DP_AXIS = "dp"

_LANE = None


@dataclasses.dataclass(frozen=True)
class Lane:
    """Shard coordinates of the current shard_map lane."""

    n_kv: int = 1
    n_dp: int = 1
    kv_axis: str = KV_AXIS
    dp_axis: str = DP_AXIS

    # -- head axis (kv) -----------------------------------------------------
    def heads(self, h: int) -> int:
        """Local head count for an ``h``-head global axis."""
        assert h % self.n_kv == 0, (h, self.n_kv)
        return h // self.n_kv

    def split(self, x: Array, axis: int) -> Array:
        """This shard's contiguous head block along ``axis``.

        Works for attention heads too (H = G * H_kv, kv-grouped
        contiguously), not just KV heads.
        """
        loc = self.heads(x.shape[axis])
        i = jax.lax.axis_index(self.kv_axis)
        return jax.lax.dynamic_slice_in_dim(x, i * loc, loc, axis)

    def merge(self, x: Array, axis: int, full: int, owned=None) -> Array:
        """Scatter the local head block into a zeros buffer and psum.

        Contributions are disjoint — distinct head blocks over ``kv``,
        and (when ``owned`` partitions rows) distinct row blocks over
        ``dp`` — so each merged cell is ``x + 0 + ... + 0``: exactly the
        single-device value. ``owned``: bool [B] row mask from
        ``owned_rows`` (row dim must be axis 0), or None when rows were
        not partitioned.
        """
        if owned is not None:
            own = owned.reshape(owned.shape + (1,) * (x.ndim - 1))
            x = jnp.where(own, x, jnp.zeros_like(x))
        loc = x.shape[axis]
        if full != loc:
            start = [0] * x.ndim
            start[axis] = jax.lax.axis_index(self.kv_axis) * loc
            shape = list(x.shape)
            shape[axis] = full
            x = jax.lax.dynamic_update_slice(
                jnp.zeros(shape, x.dtype), x, tuple(start))
        axes = [a for a, n in ((self.kv_axis, self.n_kv),) if n > 1]
        if owned is not None and self.n_dp > 1:
            axes.append(self.dp_axis)
        return jax.lax.psum(x, tuple(axes)) if axes else x

    # -- row axis (dp) ------------------------------------------------------
    def owned_rows(self, n_rows: int):
        """Bool [n_rows] mask of the rows this dp shard computes, or None
        when rows are not partitioned (``n_dp == 1``, or ``n_rows`` not
        divisible — every shard then computes every row, still exact)."""
        if self.n_dp == 1 or n_rows % self.n_dp:
            return None
        per = n_rows // self.n_dp
        i = jax.lax.axis_index(self.dp_axis)
        return (jnp.arange(n_rows) // per) == i

    def mask_read(self, cache_l, owned):
        """Counter-masked attention-read view of a layer cache: non-owned
        rows span zero context/residual tokens (their attention output is
        then exact 0.0). Only the READ is masked — appends and commits
        always use the unmasked cache so replicated state stays identical
        on every shard."""
        if owned is None:
            return cache_l
        zero = lambda n: jnp.where(owned, n, jnp.zeros_like(n))
        return dataclasses.replace(
            cache_l, n_comp=zero(cache_l.n_comp), n_resid=zero(cache_l.n_resid))


def active_lane() -> Lane | None:
    """The Lane of the current shard_map trace, or None outside one."""
    return _LANE


def local_heads(h: int) -> int:
    """``h`` heads as seen by the current lane (global count outside)."""
    lane = active_lane()
    return lane.heads(h) if lane is not None else h


@contextlib.contextmanager
def lane_scope(lane: Lane):
    global _LANE
    prev, _LANE = _LANE, lane
    try:
        yield lane
    finally:
        _LANE = prev


def sharded_call(fn, mesh, in_specs, out_specs):
    """shard_map ``fn`` with a Lane installed for the duration of its
    (synchronous) trace, so model code can ask ``active_lane()``."""
    lane = Lane(n_kv=int(mesh.shape.get(KV_AXIS, 1)),
                n_dp=int(mesh.shape.get(DP_AXIS, 1)))

    def local(*args):
        with lane_scope(lane):
            return fn(*args)

    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
