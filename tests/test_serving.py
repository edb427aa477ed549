"""Serving engine: calibration, generate determinism, continuous batching."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.core.cache import PackKVConfig
from repro.models import get_model
from repro.serving import Engine, EngineConfig, Request, SlotServer


def _seed0_params(cfg):
    """Smoke weights from PRNGKey(0) as drawn before JAX 0.5 turned on
    ``jax_threefry_partitionable``, which changed the bits a key yields.
    The greedy-agreement tests below hold only where no decode step has a
    top-2 logit margin within the paths' bf16 noise; the newer draw puts a
    one-ulp margin (exact policy vs rel_scale 0.005) and an exact bf16 tie
    (chunked vs monolithic admission) on their steps."""
    with jax.threefry_partitionable(False):
        return get_model(cfg).init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def llama_engine():
    cfg = SMOKES["llama2-7b"]
    params = _seed0_params(cfg)
    return Engine(cfg, params, PackKVConfig(),
                  EngineConfig(capacity=256, max_batch=2, calib_tokens=128)), cfg


def test_calibration_sets_static_specs(llama_engine):
    eng, cfg = llama_engine
    assert eng.pack_cfg.k_spec_static is not None
    assert eng.pack_cfg.k_spec_static.head_dim == cfg.hd
    assert eng.pack_cfg.v_spec_static.head_dim == cfg.hd


def test_generate_deterministic(llama_engine, rng):
    eng, cfg = llama_engine
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 64)), jnp.int32)
    a, _ = eng.generate({"tokens": toks}, max_new=6)
    b, _ = eng.generate({"tokens": toks}, max_new=6)
    assert (a == b).all()
    assert a.shape == (2, 6)


def test_exact_policy_agrees_with_tight_compression(rng):
    """At rel_scale→0 the PackKV engine must produce the same greedy tokens
    as the uncompressed engine."""
    cfg = SMOKES["llama2-7b"]
    params = _seed0_params(cfg)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 64)), jnp.int32)
    ecfg = EngineConfig(capacity=256, max_batch=1, calib_tokens=128)
    e_none = Engine(cfg, params, PackKVConfig(policy="none"), ecfg)
    e_pack = Engine(
        cfg, params,
        PackKVConfig(k_rel_scale=0.005, v_rel_scale=0.005), ecfg,
    )
    a, _ = e_none.generate({"tokens": toks}, max_new=5)
    b, _ = e_pack.generate({"tokens": toks}, max_new=5)
    assert (a == b).all(), (a, b)


def test_chunked_admission_counts_and_stall_bound(llama_engine, rng):
    """Chunked admission splits a long prompt into page-bounded segments
    and never runs more than one prefill task at a time (bounded decode
    stall); the legacy monolithic path (chunk budget 0) gives the same
    greedy tokens."""
    base, cfg = llama_engine
    eng = Engine(cfg, base.params, base.pack_cfg,
                 dataclasses.replace(base.ecfg, page_size=64,
                                     calibrate=False))
    page = eng.ecfg.page_size
    reqs = lambda: [Request(rid=rid, max_new=4,
                            tokens=rng.integers(0, cfg.vocab, 3 * page + 7))
                    for rid in range(3)]
    rng_state = rng.bit_generator.state
    srv = SlotServer(eng)
    for r in reqs():
        srv.submit(r)
    srv.run()
    # 3*page+7 tokens at a 1-page budget -> 4 segments per request
    assert srv.stats.prefill_chunks == 3 * 4

    rng.bit_generator.state = rng_state
    mono = SlotServer(
        Engine(cfg, eng.params, eng.pack_cfg,
               dataclasses.replace(eng.ecfg, prefill_chunk_pages=0,
                                   calibrate=False)))
    for r in reqs():
        mono.submit(r)
    mono.run()
    assert mono.stats.prefill_chunks == 0
    for rid in srv.done:
        np.testing.assert_array_equal(srv.done[rid].output,
                                      mono.done[rid].output)


@pytest.mark.parametrize("policy", ["packkv", "none"])
def test_slot_server_matches_per_request_generate(rng, policy):
    """Heterogeneous prompts/max_new through the continuous scheduler give
    the SAME greedy tokens as Engine.generate run per-request (B=1), and a
    freed slot is reused within the run."""
    cfg = SMOKES["llama2-7b"]
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, PackKVConfig(policy=policy),
                 EngineConfig(capacity=256, max_batch=2, calib_tokens=128))
    reqs = [
        Request(rid=0, max_new=3, tokens=rng.integers(0, cfg.vocab, 50)),
        Request(rid=1, max_new=8, tokens=rng.integers(0, cfg.vocab, 70)),
        Request(rid=2, max_new=5, tokens=rng.integers(0, cfg.vocab, 50)),
        Request(rid=3, max_new=2, tokens=rng.integers(0, cfg.vocab, 30)),
        Request(rid=4, max_new=1, tokens=rng.integers(0, cfg.vocab, 30)),
    ]
    srv = SlotServer(eng)
    for r in reqs:
        srv.submit(r)
    finished = srv.run()
    # run() returns every request, including admit-time retirements (max_new=1)
    assert len(finished) == len(reqs) == len(srv.done)
    # more requests than slots completed -> at least one slot was recycled
    assert srv.stats.slot_reuses >= 1
    assert srv.stats.completed == 5
    assert 0.0 < srv.stats.occupancy <= 1.0
    for r in reqs:
        want, _ = eng.generate(
            {"tokens": jnp.asarray(r.tokens[None], jnp.int32)}, r.max_new
        )
        np.testing.assert_array_equal(srv.done[r.rid].output, want[0])


def test_slot_server_rejects_zero_max_new(rng):
    cfg = SMOKES["llama2-7b"]
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, PackKVConfig(policy="none"),
                 EngineConfig(capacity=256, max_batch=1, calibrate=False))
    srv = SlotServer(eng)
    with pytest.raises(ValueError, match="max_new"):
        srv.submit(Request(rid=0, max_new=0,
                           tokens=rng.integers(0, cfg.vocab, 8)))


def test_slot_server_eos_eviction(rng):
    """A request that emits eos stops early and frees its slot."""
    cfg = SMOKES["llama2-7b"]
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, PackKVConfig(policy="none"),
                 EngineConfig(capacity=256, max_batch=1, calib_tokens=128))
    toks = rng.integers(0, cfg.vocab, 40)
    probe, _ = eng.generate({"tokens": jnp.asarray(toks[None], jnp.int32)}, 4)
    eos = int(probe[0, 1])  # force eos on the 2nd generated token
    srv = SlotServer(eng, eos_id=eos)
    srv.submit(Request(rid=0, max_new=16, tokens=toks))
    srv.run()
    out = srv.done[0].output
    assert len(out) == 2 and out[-1] == eos
    assert srv.slots == [None]


def test_recurrent_families_reject_prefix_cache():
    """rwkv6 / rglru decode state has no page-addressable KV pages:
    --prefix-cache must fail loudly at engine build (the check fires before
    params are touched), not be silently ignored at admission time."""
    for name in ("rwkv6-1.6b", "recurrentgemma-9b"):
        cfg = SMOKES[name]
        with pytest.raises(ValueError, match="prefix-cache"):
            Engine(cfg, None, PackKVConfig(policy="none"),
                   EngineConfig(capacity=256, paged=True, prefix_cache=True))


def test_rglru_engine_windowed(rng):
    cfg = SMOKES["recurrentgemma-9b"]
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, PackKVConfig(residual=96),
                 EngineConfig(capacity=cfg.window, max_batch=1, calib_tokens=128))
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 200)), jnp.int32)  # > window
    out, state = eng.generate({"tokens": toks}, max_new=4)
    assert out.shape == (1, 4)
    assert state.pos.shape == (1,)  # per-row positions (slot recycling)
    assert int(state.pos[0]) == 204
