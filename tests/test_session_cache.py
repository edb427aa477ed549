"""Multi-turn session cache (ISSUE 9): park/resume exactness against the
REAL engine.

The core claim: a returning session served from a parked entry is
bit-identical to the SAME conversation decoded without interruption,
across {xla, pallas} x {packkv, none} x {dense, paged, prefix} — with
ZERO forward passes over the restored context. The argument mirrors
preemption exactness (placement independence: parked bytes are the row's
exact compressed pages + residual + counters + calibration) plus
teacher-forced suffix ingestion: the new turn's unseen tokens stream
through ordinary decode launches whose argmax is overridden by the
already-known next prompt token, so the cache the suffix builds is the
one an uninterrupted decode would have built.

The control is a manual drive on a session-off engine of the same
calibrated config: prefill turn 1, greedy-decode it, teacher-force the
extension, greedy-decode turn 2. NOTE the control must prefill through
the SAME path as the server (``insert_request_prefix`` when the prefix
cache is on): the prefix and plain prefill paths calibrate channel
permutations differently, which is cross-path behavior under test
elsewhere, not a park/resume property.

Also here: the disk spill tier (LRU victims survive a host-capacity
squeeze byte-exactly via the savable-dtype mini serializers), TTL expiry
degrading to a cold admission, a 3-resume conversation chain, parked
shared-prefix pages, and the loud rejections (sliding-window attention,
recurrent families).
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.core.cache import PackKVConfig, SessionStore
from repro.models import get_model
from repro.serving import Engine, EngineConfig, Request, SlotServer
from repro.utils import cpu_child_env

PAGE = 128


@pytest.fixture(scope="module")
def smoke_setup():
    cfg = SMOKES["llama2-7b"]
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, policy, backend, mode, **kw):
    paged = mode != "dense"
    return Engine(
        cfg, params, PackKVConfig(policy=policy),
        EngineConfig(capacity=512, max_batch=2, calib_tokens=128,
                     decode_chunk=4, bucketed=True, bucket_unit=64,
                     backend=backend, paged=paged, page_size=PAGE,
                     prefix_cache=(mode == "prefix"),
                     debug_invariants=paged, prefill_chunk_pages=1,
                     session_cache=True, **kw))


def _control_chain(src: Engine, prompt, turns):
    """Uninterrupted manual drive of a whole conversation on a session-off
    engine of the same calibrated config: ``turns`` is ``[(ext, max_new),
    ...]`` with ``ext is None`` for turn 1. Returns one output list per
    turn."""
    base = Engine(src.cfg, src.params, src.pack_cfg,
                  dataclasses.replace(src.ecfg, max_batch=1,
                                      session_cache=False, preempt=False,
                                      calibrate=False, spec_decode=False))
    cache = base.alloc_slot_cache()
    if base.ecfg.prefix_cache:
        logits, cache = base.insert_request_prefix(cache, 0, prompt, [], None)
    else:
        logits, cache = base.insert_request(cache, 0, prompt)
    t = int(jnp.argmax(logits))
    outs = []
    for ext, max_new in turns:
        if ext is not None:
            # teacher-force the extension: the previous turn's last token
            # seeds the first launch, the extension's last token seeds the
            # new turn's first real argmax
            for f in [outs[-1][-1]] + [int(x) for x in ext[:-1]]:
                _, cache = base.decode(cache, jnp.asarray([[f]]), None)
            lg, cache = base.decode(cache, jnp.asarray([[int(ext[-1])]]),
                                    None)
            t = int(jnp.argmax(lg, -1)[0])
        out = [t]
        for _ in range(max_new - 1):
            lg, cache = base.decode(cache, jnp.asarray([[t]]), None)
            t = int(jnp.argmax(lg, -1)[0])
            out.append(t)
        outs.append(out)
    return outs


MODES = ("dense", "paged", "prefix")
MATRIX = [(p, b, m) for p in ("packkv", "none") for b in ("xla", "pallas")
          for m in MODES]


@pytest.mark.parametrize("policy,backend,mode", MATRIX)
def test_session_hit_bit_identical(smoke_setup, policy, backend, mode):
    cfg, params = smoke_setup
    eng = _engine(cfg, params, policy, backend, mode)
    srv = SlotServer(eng)
    r = np.random.default_rng(5)
    prompt = r.integers(0, cfg.vocab, 200)
    srv.submit(Request(rid=0, max_new=8, tokens=prompt))
    srv.run()
    assert srv.stats.session_parks == 1, "retirement never parked"
    out1 = list(srv.done[0].output)

    ext = r.integers(0, cfg.vocab, 5)
    chunks_before = srv.stats.prefill_chunks
    srv.submit(Request(rid=1, max_new=6, tokens=np.concatenate(
        [prompt, np.asarray(out1), ext])))
    srv.run()
    assert srv.stats.session_hits == 1, "returning session missed"
    # zero forward passes over the restored context: the hit admits via
    # one restore scatter, never a prefill chunk
    assert srv.stats.prefill_chunks == chunks_before
    if mode != "dense":
        assert srv.stats.session_restored_pages > 0
    out2 = list(srv.done[1].output)

    c1, c2 = _control_chain(eng, prompt, [(None, 8), (ext, 6)])
    assert out1 == c1, f"turn 1 diverged: {out1} != {c1}"
    assert out2 == c2, f"session hit diverged: {out2} != {c2}"


def test_session_three_resume_chain(smoke_setup):
    """A 4-turn conversation resumes 3 times, each turn bit-identical to
    the uninterrupted chain (the re-park after each turn snapshots the
    grown trace, so every resume extends the previous one)."""
    cfg, params = smoke_setup
    eng = _engine(cfg, params, "packkv", "xla", "paged")
    srv = SlotServer(eng)
    r = np.random.default_rng(7)
    prompt = r.integers(0, cfg.vocab, 150)
    plan = [(None, 6), (r.integers(0, cfg.vocab, 4), 4),
            (r.integers(0, cfg.vocab, 1), 5), (r.integers(0, cfg.vocab, 3), 4)]
    outs = []
    toks = prompt
    for rid, (ext, max_new) in enumerate(plan):
        if ext is not None:
            toks = np.concatenate([toks, np.asarray(outs[-1]), ext])
        srv.submit(Request(rid=rid, max_new=max_new, tokens=toks))
        srv.run()
        outs.append(list(srv.done[rid].output))
    assert srv.stats.session_parks == 4 and srv.stats.session_hits == 3
    assert srv.stats.session_hit_rate == 0.75
    ctl = _control_chain(eng, prompt, plan)
    for k, (got, want) in enumerate(zip(outs, ctl)):
        assert got == want, f"turn {k} diverged: {got} != {want}"


def test_session_disk_tier_roundtrip(smoke_setup, tmp_path):
    """A 1-byte host tier forces the park straight to disk through the
    savable-dtype mini serializers; the returning session promotes it back
    and is still bit-identical — the spill is byte-exact."""
    cfg, params = smoke_setup
    store = SessionStore(capacity_bytes=1, disk_dir=str(tmp_path))
    eng = _engine(cfg, params, "packkv", "xla", "paged")
    srv = SlotServer(eng, session_store=store)
    r = np.random.default_rng(9)
    prompt = r.integers(0, cfg.vocab, 180)
    srv.submit(Request(rid=0, max_new=8, tokens=prompt))
    srv.run()
    assert store.spills == 1 and len(store._host) == 0
    assert len(store._disk) == 1, "park never spilled to disk"
    out1 = list(srv.done[0].output)
    ext = r.integers(0, cfg.vocab, 4)
    srv.submit(Request(rid=1, max_new=6, tokens=np.concatenate(
        [prompt, np.asarray(out1), ext])))
    srv.run()
    assert store.loads == 1, "hit never promoted from disk"
    assert srv.stats.session_hits == 1
    out2 = list(srv.done[1].output)
    c1, c2 = _control_chain(eng, prompt, [(None, 8), (ext, 6)])
    assert (out1, out2) == (c1, c2)


def test_session_ttl_expiry_degrades_to_cold(smoke_setup):
    """An expired park is a MISS, never a crash: the returning session
    re-prefills cold and (losslessly, policy=none) still matches the
    uninterrupted chain."""
    cfg, params = smoke_setup
    now = [0.0]
    store = SessionStore(ttl_s=10.0, clock=lambda: now[0])
    eng = _engine(cfg, params, "none", "xla", "dense")
    srv = SlotServer(eng, session_store=store)
    r = np.random.default_rng(3)
    prompt = r.integers(0, cfg.vocab, 150)
    srv.submit(Request(rid=0, max_new=6, tokens=prompt))
    srv.run()
    assert len(store) == 1
    out1 = list(srv.done[0].output)
    now[0] = 11.0  # the park is now stale
    ext = r.integers(0, cfg.vocab, 4)
    srv.submit(Request(rid=1, max_new=5, tokens=np.concatenate(
        [prompt, np.asarray(out1), ext])))
    srv.run()
    assert store.expired == 1 and srv.stats.session_hits == 0
    assert srv.stats.session_evictions == 1
    assert srv.done[1].status == "done"
    c1, c2 = _control_chain(eng, prompt, [(None, 6), (ext, 5)])
    assert list(srv.done[0].output) == c1
    assert list(srv.done[1].output) == c2  # lossless: cold == chain


def test_session_shared_prefix_park(smoke_setup):
    """A parked session whose prefix pages live in the trie re-maps them
    by REFERENCE on return: the parked meta pins ``n_shared`` pages, the
    restore streams back only the owned ones, and the resumed output is
    exact."""
    cfg, params = smoke_setup
    eng = _engine(cfg, params, "packkv", "xla", "prefix")
    srv = SlotServer(eng)
    r = np.random.default_rng(11)
    sys_p = r.integers(0, cfg.vocab, 2 * PAGE)
    a = np.concatenate([sys_p, r.integers(0, cfg.vocab, 40)])
    b = np.concatenate([sys_p, r.integers(0, cfg.vocab, 53)])
    srv.submit(Request(rid=0, max_new=6, tokens=a))
    srv.run()
    srv.submit(Request(rid=1, max_new=6, tokens=b))  # B shares A's prefix
    srv.run()
    assert srv.stats.session_parks == 2
    assert srv.stats.prefix_hits == 1, "B never shared A's prefix pages"
    out_b = list(srv.done[1].output)
    trace_b = np.concatenate([b, np.asarray(out_b)])
    key = srv._sessions.match(trace_b)
    assert key is not None
    meta = srv._sessions.meta(key)
    assert meta["n_shared"] >= 2, "parked meta lost the shared-prefix pin"
    ext = r.integers(0, cfg.vocab, 4)
    restored_before = srv.stats.session_restored_pages
    srv.submit(Request(rid=2, max_new=5,
                       tokens=np.concatenate([trace_b, ext])))
    srv.run()
    assert srv.stats.session_hits == 1
    # only the OWNED pages streamed back; the shared ones re-mapped free
    assert (srv.stats.session_restored_pages - restored_before
            == meta["n_pages"] - meta["n_shared"])
    assert srv.done[2].status == "done" and len(srv.done[2].output) == 5


_SHARDED_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import SMOKES
from repro.core.cache import PackKVConfig
from repro.models import get_model
from repro.serving import Engine, EngineConfig, Request, SlotServer

cfg = SMOKES["llama2-7b"]
params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
PAGE = 128


def build(mesh_shape, session):
    return Engine(cfg, params, PackKVConfig(policy="packkv"),
                  EngineConfig(capacity=512, max_batch=1, calib_tokens=128,
                               bucket_unit=64, paged=True, page_size=PAGE,
                               session_cache=session, mesh_shape=mesh_shape))


r = np.random.default_rng(5)
prompt = r.integers(0, cfg.vocab, 200)
ext = r.integers(0, cfg.vocab, 5)

# the park/hit drive on the kv-sharded engine
srv = SlotServer(build((1, 2), session=True))
srv.submit(Request(rid=0, max_new=8, tokens=prompt))
srv.run()
out1 = list(map(int, srv.done[0].output))
srv.submit(Request(rid=1, max_new=6, tokens=np.concatenate(
    [prompt, np.asarray(out1), ext])))
srv.run()
hits = srv.stats.session_hits
out2 = list(map(int, srv.done[1].output))

# the cold control: same mesh, session cache OFF, manual uninterrupted
# drive of the whole conversation (parked bytes vs recompute must agree)
base = build((1, 2), session=False)
cache = base.alloc_slot_cache()
logits, cache = base.insert_request(cache, 0, prompt)
t = int(jnp.argmax(logits))
c1 = [t]
for _ in range(7):
    lg, cache = base.decode(cache, jnp.asarray([[t]]), None)
    t = int(jnp.argmax(lg, -1)[0])
    c1.append(t)
for f in [c1[-1]] + [int(x) for x in ext[:-1]]:
    _, cache = base.decode(cache, jnp.asarray([[f]]), None)
lg, cache = base.decode(cache, jnp.asarray([[int(ext[-1])]]), None)
t = int(jnp.argmax(lg, -1)[0])
c2 = [t]
for _ in range(5):
    lg, cache = base.decode(cache, jnp.asarray([[t]]), None)
    t = int(jnp.argmax(lg, -1)[0])
    c2.append(t)
print("RESULT " + json.dumps({"hits": hits, "out1": out1, "out2": out2,
                              "c1": c1, "c2": c2}))
"""


@pytest.mark.slow
def test_session_hit_matches_cold_on_mesh():
    """ISSUE 10: park/resume on a kv-sharded mesh. The parked mini gathers
    shard-local payloads into the same dense full-head format as
    single-device parks, and the restore re-shards through the lane
    in_specs — so a session HIT on the mesh must equal the uninterrupted
    cold drive on the same mesh, bit for bit."""
    import subprocess
    import sys
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_CHILD], capture_output=True,
        text=True,
        env=cpu_child_env(),
        cwd=".", timeout=900,
    )
    lines = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    assert lines, f"child failed:\n{r.stderr[-2000:]}"
    res = json.loads(lines[0][7:])
    assert res["hits"] == 1, "returning session missed on the mesh"
    assert res["out1"] == res["c1"], "turn 1 diverged on the mesh"
    assert res["out2"] == res["c2"], "sharded session hit != cold drive"


def test_session_rejects_sliding_window(smoke_setup):
    _, params = smoke_setup
    cfg = SMOKES["recurrentgemma-9b"]  # window=128
    with pytest.raises(ValueError, match="sliding-window"):
        _engine(cfg, params, "none", "xla", "dense")


def test_session_rejects_recurrent_family(smoke_setup):
    _, params = smoke_setup
    cfg = SMOKES["rwkv6-1.6b"]  # pure recurrent: no evacuate/restore ops
    with pytest.raises(ValueError, match="session-cache"):
        _engine(cfg, params, "none", "xla", "dense")
