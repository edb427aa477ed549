"""The serving engine's telemetry: the paged attention kernel's grid-step
counters (all, dead, field-major) and the prefill token counter against
brute-force recounts, and the names its programs and kernels carry into a
compiled module."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.core.cache import PackKVConfig
from repro.core.tiered import TierSpec
from repro.kernels.packed_attention import PAGED_KERNEL
from repro.models import get_model
from repro.serving import Engine, EngineConfig, Request, SlotServer

PAGE = 64
CAPACITY = 512
SLOTS = 3
# prompt -> max_new: the rows' cached-token ranges never overlap, so a
# logged row's count names its prompt; the 40-token row outgrows its
# residual (128) and flushes a block mid-run
REQUESTS = {40: 100, 200: 10, 330: 10, 460: 10}


@pytest.fixture(scope="module")
def smoke():
    cfg = SMOKES["llama2-7b"]
    return cfg, get_model(cfg).init(jax.random.PRNGKey(0), cfg)


def _engine(smoke, pack=PackKVConfig(), **kw):
    cfg, params = smoke
    ecfg = dict(capacity=CAPACITY, max_batch=SLOTS, calib_tokens=128,
                bucket_unit=PAGE, page_size=PAGE, decode_chunk=4)
    ecfg.update(kw)
    return Engine(cfg, params, pack, EngineConfig(**ecfg))


def _serve(eng, requests=REQUESTS):
    srv = SlotServer(eng)
    rng = np.random.default_rng(1)
    for rid, (n, max_new) in enumerate(requests.items()):
        srv.submit(Request(rid=rid, max_new=max_new, tokens=rng.integers(
            0, eng.cfg.vocab, n).astype(np.int32)))
    srv.run()
    return srv


def _n_comp(prompt: int, cached: int, pack: PackKVConfig) -> int:
    """Compressed tokens of a row holding ``cached`` tokens, one append at
    a time: prefill compresses whole blocks; an append that finds a full
    residual flushes one block first, while the region has room."""
    n = prompt // pack.block * pack.block
    r = prompt - n
    for _ in range(cached - prompt):
        if r >= pack.residual and n + pack.block <= CAPACITY:
            n, r = n + pack.block, r - pack.block
        r += 1
    return n


@pytest.mark.parametrize("decode_chunk", [4, 1])
def test_grid_step_counters_match_a_recount_of_the_launch_log(smoke,
                                                              decode_chunk):
    eng = _engine(smoke, backend="pallas", paged=True, log_launches=True,
                  decode_chunk=decode_chunk)
    srv = _serve(eng)
    cfg, pack = eng.cfg, eng.pack_cfg
    grid = dead = 0
    flushed = False
    for n_exec, bucket, cached in srv.stats.launches:
        n_pg = bucket // PAGE
        for s in range(1, n_exec + 1):
            live = 0
            for c in cached:
                prompt = max(p for p in REQUESTS if p <= c)
                n = _n_comp(prompt, c + s, pack)
                flushed |= n > prompt // pack.block * pack.block
                live += min(-(-n // PAGE), n_pg)
            # free rows hold no compressed token: every step of theirs is dead
            steps = SLOTS * n_pg
            grid += cfg.n_layers * cfg.n_kv_heads * steps
            dead += cfg.n_layers * cfg.n_kv_heads * (steps - live)
    assert flushed  # the recurrence was exercised past a flush
    assert srv.stats.attn_grid_steps == grid
    assert srv.stats.attn_dead_steps == dead
    assert 0 < dead < grid
    # calibration gives the smoke one 4-bit tier: every step is field-major
    assert srv.stats.attn_field_major_steps == grid
    got = srv.stats.to_json()
    assert (got["attn_grid_steps"], got["attn_dead_steps"]) == (grid, dead)


@pytest.mark.parametrize("widths", [(4,), (2, 4, 8)], ids=["one", "mixed"])
def test_field_major_steps_count_single_width_launches(smoke, widths):
    cfg, _ = smoke
    spec = TierSpec.for_head_dim(cfg.hd, widths, (1,) if len(widths) == 1
                                 else (0.25, 0.5, 0.25))
    pack = PackKVConfig(k_spec_static=spec, v_spec_static=spec)
    srv = SlotServer(_engine(smoke, backend="pallas", paged=True,
                             calibrate=False, pack=pack))
    grid, dead, field = srv._count_attn([[100, 0, 300]], 512)
    assert 0 < dead < grid
    assert field == (grid if len(widths) == 1 else 0)
    assert srv.stats.attn_field_major_steps == field


def test_no_grid_steps_where_no_paged_kernel_runs(smoke):
    srv = _serve(_engine(smoke, backend="xla", paged=True),
                 {40: 6, 200: 6})
    assert srv.stats.decode_steps > 0
    assert srv.stats.attn_grid_steps == srv.stats.attn_dead_steps == 0


@pytest.mark.parametrize("chunk_pages", [1, 0], ids=["chunked", "monolithic"])
def test_prefill_tokens_count_the_admitted_prompts(smoke, chunk_pages):
    requests = {40: 3, 200: 3, 330: 3}
    srv = _serve(_engine(smoke, backend="xla", paged=True,
                         prefill_chunk_pages=chunk_pages), requests)
    assert srv.stats.admitted == len(requests)
    assert srv.stats.prefill_tokens == sum(requests)
    if chunk_pages:  # 200 and 330 took more than one chunk of 64
        assert srv.stats.prefill_chunks > 0


def test_programs_and_the_paged_kernel_carry_their_names(smoke):
    eng = _engine(smoke, backend="pallas", paged=True)
    cache = eng.alloc_slot_cache()
    decode = eng._decode_multi.lower(
        eng.params, cache=cache, token=jnp.zeros((SLOTS, 1), jnp.int32),
        active=jnp.ones(SLOTS, bool), n_steps=jnp.int32(1),
        eos_id=jnp.int32(-1), t_max=4, n_bucket=256)
    assert decode.as_text().startswith("module @jit_packkv_decode_multi ")
    assert PAGED_KERNEL in decode.as_text(debug_info=True)
    tokens = {"tokens": jnp.zeros((1, 70), jnp.int32)}
    insert = eng._insert.lower(eng.params, cache=cache, slot=jnp.int32(0),
                               batch=tokens)
    assert insert.as_text().startswith("module @jit_packkv_insert ")
    scratch = eng.chunk_init(130)
    step = eng._chunk_step.lower(eng.params, scratch=scratch,
                                 tokens=tokens["tokens"][:, :64], n_ctx=0)
    assert step.as_text().startswith("module @jit_packkv_chunk_step ")
