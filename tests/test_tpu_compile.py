"""Every packed Pallas kernel compiles for a TPU v5e at minitron-4b widths.

The TPU compiler is installed with JAX, so a kernel can be compiled for a
chip that is described and not attached: a Mosaic refusal (a block shape
the tiling rejects, an unaligned dynamic slice, too much VMEM) shows up
here at no chip time. Nothing runs, so this checks lowering only; the
interpret-mode tests check the numbers.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library at once, and pytest-xdist
workers import every test file.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.core.tiered import TierSpec, alloc_tiered, alloc_tiered_pool
from repro.kernels import pallas_utils
from repro.kernels.kpack_matvec import kpack_tier_scores, kpack_tier_scores_paged
from repro.kernels.packed_attention import (
    fused_packed_attention,
    fused_packed_attention_paged,
)
from repro.kernels.vpack_matvec import vpack_tier_out, vpack_tier_out_paged

# minitron-4b decode at the smoke's serving shape
B, H_KV, G, D = 4, 8, 3, 128
CAPACITY, PAGE = 2048, 256
POOL_PAGES = B * CAPACITY // PAGE
SPEC = TierSpec(widths=(2, 4, 8), counts=(32, 64, 32))
# what calibration picks for the benchmark's models: the field-major decode
SPEC_4BIT = TierSpec(widths=(4,), counts=(D,))
SM = D ** -0.5


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels lower through Mosaic (the CPU default is the interpreter);
    the persistent compile cache is off, since an entry compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pallas_utils, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _flat(tier):
    """A dense tier's (payload, mins, shifts) as the kernels' [BH, C, .]."""
    return [jax.ShapeDtypeStruct((B * H_KV, *x.shape[2:]), x.dtype)
            for x in (tier.payload, tier.mins, tier.shifts)]


def _launch(kernel):
    """(function, abstract args) of one kernel launch at minitron widths."""
    dense = jax.eval_shape(lambda: alloc_tiered(B, H_KV, CAPACITY, SPEC))
    pool = jax.eval_shape(
        lambda: alloc_tiered_pool(B, H_KV, POOL_PAGES, PAGE, SPEC))
    tier, ptier = dense.tiers[0], pool.tiers[0]  # the 2-bit tier: narrowest
    C = SPEC.counts[0]
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    table = i32(B, CAPACITY // PAGE)
    tier_kw = dict(width=tier.width, pack_size=SPEC.pack_size)
    if kernel == "attention":
        return (partial(fused_packed_attention, sm_scale=SM),
                [f32(B, H_KV * G, D), dense, dense, i32(B)])
    if kernel in ("attention_paged", "attention_paged_4bit"):
        if kernel == "attention_paged_4bit":
            pool = jax.eval_shape(
                lambda: alloc_tiered_pool(B, H_KV, POOL_PAGES, PAGE, SPEC_4BIT))
        return (partial(fused_packed_attention_paged, n_tokens=CAPACITY,
                        sm_scale=SM, page_size=PAGE),
                [f32(B, H_KV * G, D), pool, pool, table, i32(B)])
    if kernel == "kpack":
        return (lambda p, m, s, q, n: kpack_tier_scores(
                    p, m, s, q, n_valid=n, **tier_kw),
                [*_flat(tier), f32(B * H_KV, G, C), i32(B * H_KV)])
    if kernel == "kpack_paged":
        return (partial(kpack_tier_scores_paged, n_tokens=CAPACITY,
                        page_size=PAGE, **tier_kw),
                [ptier.payload, ptier.mins, ptier.shifts,
                 f32(B * H_KV, G, C), table, i32(B * H_KV)])
    if kernel == "vpack":
        return (lambda p, m, s, w, n: vpack_tier_out(
                    p, m, s, w, n_valid=n, **tier_kw),
                [*_flat(tier), f32(B * H_KV, G, CAPACITY), i32(B * H_KV)])
    assert kernel == "vpack_paged"
    return (partial(vpack_tier_out_paged, page_size=PAGE, **tier_kw),
            [ptier.payload, ptier.mins, ptier.shifts,
             f32(B * H_KV, G, CAPACITY), table, i32(B * H_KV)])


@pytest.mark.parametrize("kernel", ["attention", "attention_paged", "kpack",
                                    "kpack_paged", "vpack", "vpack_paged",
                                    "attention_paged_4bit"])
def test_kernel_compiles_for_v5e(one_chip, compiled_kernels, kernel):
    fn, args = _launch(kernel)
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
