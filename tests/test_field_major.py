"""Field-major tile decode in the attention kernel.

Invariants under test (interpret mode on CPU, small shapes):
  * ``decode_tier_tile_fields`` is ``decode_tier_tile`` with its lanes in
    field-major order, at every width, and ``token_iota`` /
    ``field_major_lanes`` name and reorder lanes by the same permutation.
  * One 4-bit tier (what calibration picks for the benchmark's models): the
    fused kernel matches the XLA reference, and the paged launch stays
    bit-identical to the dense one, at rows whose live length ends mid-word
    and mid-page.
  * Mixed widths keep the stored order and the lane-repeat decode.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tiered import TierSpec, pack_tier, pack_tiered
from repro.kernels import ops
from repro.kernels import packed_attention as pa
from repro.kernels.pallas_utils import field_major_lanes, token_iota
from repro.kernels.unpack import decode_tier_tile, decode_tier_tile_fields

WIDTHS = [1, 2, 4, 8, 16]
B, H, G, D = 3, 1, 2, 32
L, PAGE, TILE, R = 128, 64, 64, 8
# live lengths ending mid-word at every width and mid-page (37, 70), and
# one token short of the bucket
N_COMP = (37, 70, 127)
SM = D ** -0.5


def _perm(TL: int, k: int) -> np.ndarray:
    """Source token of each lane of a field-major tile."""
    lane = np.arange(TL)
    m = TL // k
    return (lane % m) * k + lane // m


@pytest.mark.parametrize("width,pack_size",
                         [(w, 8) for w in WIDTHS] + [(1, 16), (4, 16)])
def test_field_major_decode_reorders_the_stored_decode(rng, width, pack_size):
    # at 8 tokens a pack: packs span words (8, 16 bits), equal them (4)
    # and split them (1, 2); at 16 (the other supported pack) a 4-bit pack
    # spans two words and a 1-bit word holds two packs
    TL = 128
    # a range wider than the width, so shift-packs are active
    q = jnp.asarray(rng.integers(-100, 100, (16, TL)), jnp.int32)
    buf = jax.jit(pack_tier, static_argnums=(1, 2))(q, width, pack_size)
    decode = lambda f: jax.jit(partial(f, width=width, pack_size=pack_size))(
        buf.payload, buf.mins, buf.shifts)
    stored, fields = decode(decode_tier_tile), decode(decode_tier_tile_fields)
    k = 32 // width  # fields of a word
    pi = _perm(TL, k)
    np.testing.assert_array_equal(np.asarray(fields),
                                  np.asarray(stored)[:, pi])
    np.testing.assert_array_equal(np.asarray(token_iota(5, TL, k))[0], pi + 5)
    x = jnp.asarray(rng.normal(size=(4, TL)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(field_major_lanes(x, k)),
                                  np.asarray(x)[:, pi])


def _cache(rng, spec):
    """A dense TieredCache of random quantized values at ``spec``."""
    qi = rng.integers(-100, 100, (B, H, D, L))
    perm = np.stack([rng.permutation(D) for _ in range(B * H)])
    return jax.jit(pack_tiered, static_argnames="spec")(
        jnp.asarray(qi, jnp.int32), jnp.asarray(perm.reshape(B, H, D)),
        jnp.asarray(rng.uniform(0.01, 0.1, (B, H, L)), jnp.float32),
        jnp.asarray(rng.normal(size=(B, H, L)), jnp.float32), spec=spec)


@jax.jit
def _pool(tc, phys):
    """``tc``'s pages at physical ids ``phys`` (row-major over (row, page))
    of a pool layout TieredCache."""
    n_pg = L // PAGE

    def leaf(x):
        mid = x.shape[2:-1]
        y = x.reshape(B, H, *mid, n_pg, x.shape[-1] // n_pg)
        y = jnp.swapaxes(jnp.moveaxis(y, -2, 2), 0, 1)
        y = y.reshape(H, B * n_pg, *mid, y.shape[-1])
        return jnp.zeros_like(y).at[:, phys].set(y)

    tiers = tuple(dataclasses.replace(t, payload=leaf(t.payload),
                                      mins=leaf(t.mins),
                                      shifts=leaf(t.shifts))
                  for t in tc.tiers)
    return dataclasses.replace(tc, tiers=tiers, scale=leaf(tc.scale),
                               zero=leaf(tc.zero))


def _attend(rng, spec, dense=True):
    """(paged partials, dense partials or None, paged output, xla output);
    the outputs merge the compressed partials with the residual's."""
    kc, vc = _cache(rng, spec), _cache(rng, spec)
    n_pg = L // PAGE
    phys = jnp.asarray(rng.permutation(B * n_pg), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H * G, D)), jnp.float32)
    n = jnp.asarray(N_COMP, jnp.int32)
    paged = pa.fused_packed_attention_paged(
        q, _pool(kc, phys), _pool(vc, phys), phys.reshape(B, n_pg), n, L, SM,
        page_size=PAGE, tile_l=TILE)
    dense = pa.fused_packed_attention(q, kc, vc, n, SM, tile_l=TILE) \
        if dense else None
    rk, rv = (jnp.asarray(rng.normal(size=(B, H, R, D)), jnp.float32)
              for _ in range(2))
    nr = jnp.full((B,), 3, jnp.int32)
    resid = ops._residual_partials(q, rk, rv, nr, SM)
    got = ops.merge_partials(*paged, *resid)
    want = jax.jit(partial(ops.packed_decode_attention, sm_scale=SM,
                           backend="xla"))(q, kc, vc, rk, rv, n, nr)
    return paged, dense, got, want


def test_one_4bit_tier_paged_matches_reference_and_dense(rng):
    spec = TierSpec(widths=(4,), counts=(D,))
    assert pa.launch_pieces(spec, spec, TILE) == 8
    paged, dense, got, want = _attend(rng, spec)
    for p, d in zip(paged, dense):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(d))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)


def test_mixed_widths_keep_the_lane_repeat_decode(rng, monkeypatch):
    spec = TierSpec(widths=(2, 4, 8), counts=(8, 16, 8))
    assert pa.launch_pieces(spec, spec, TILE) == 1
    one = TierSpec(widths=(4,), counts=(D,))
    assert pa.launch_pieces(one, spec, TILE) == 1
    assert pa.launch_pieces(one, one, 192) == 1  # a tile of 3·64 tokens

    def refuse(*a, **k):
        raise AssertionError("mixed widths took the field-major decode")

    monkeypatch.setattr(pa, "decode_tier_tile_fields", refuse)
    _, _, got, want = _attend(rng, spec, dense=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)
