"""Sharded paged serving (ISSUE 10): mesh == single-device BIT-IDENTICAL.

The engine on a ``(dp, kv)`` mesh shards pool payloads by KV head over
``kv`` and partitions attention rows over ``dp`` while the page ledger
stays replicated (``kernels/sharded.py``). Because head sharding splits
attention into disjoint head blocks — never the softmax reduction — and
the dp merge only zeroes-and-psums rows each shard fully owns, every
float op runs in the same order on the same values as the single-device
engine. So the bar is exact equality, not tolerance: the same traffic at
``mesh_shape=(1, 1)`` and any sharded shape must emit the same tokens,
through prefill, decode, chunked admission, prefix reuse, speculative
verify, preemption swap-out/resume and session park/resume.

Multi-device cases run in a subprocess with 8 fake host devices (the
main test process stays at 1 device); quick rejection/feature-off checks
run in-process. Replaces the retired context-parallel test: the old
LSE-merge path changed reduction order and could only bound drift, the
lane path is exact.
"""
import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.core.cache import PackKVConfig
from repro.models import get_model
from repro.serving import Engine, EngineConfig
from repro.utils import cpu_child_env

_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from repro.configs import SMOKES
from repro.core.cache import PackKVConfig
from repro.models import get_model
from repro.serving import Engine, EngineConfig, Request, SlotServer

scn = json.loads(sys.argv[1])
cfg = SMOKES["llama2-7b"]
params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
PAGE = 128


def build(mesh, policy, backend, mode, **kw):
    return Engine(cfg, params, PackKVConfig(policy=policy),
                  EngineConfig(capacity=512, max_batch=2, calib_tokens=128,
                               bucket_unit=64, backend=backend,
                               paged=(mode != "dense"), page_size=PAGE,
                               prefix_cache=(mode == "prefix"),
                               mesh_shape=tuple(mesh), **kw))


def drive_plain(mesh, policy, backend, mode, spec):
    srv = SlotServer(build(mesh, policy, backend, mode, spec_decode=spec))
    r = np.random.default_rng(0)
    sys_p = (r.integers(0, cfg.vocab, 2 * PAGE) if mode == "prefix"
             else np.zeros(0, np.int64))
    for rid in range(3):
        toks = np.concatenate([sys_p, r.integers(0, cfg.vocab, 100 + rid * 30)])
        srv.submit(Request(rid=rid, max_new=6, tokens=toks))
    srv.run()
    return [list(map(int, srv.done[i].output)) for i in sorted(srv.done)]


def drive_preempt(mesh):
    # class-0 arrival against a full table forces a swap-out (test_preempt)
    srv = SlotServer(build(mesh, "packkv", "xla", "paged", preempt=True,
                           decode_chunk=4, prefill_chunk_pages=1))
    r = np.random.default_rng(11)
    sys_p = r.integers(0, cfg.vocab, 2 * PAGE)
    for rid in range(2):
        srv.submit(Request(rid=rid, max_new=40, priority=1,
                           tokens=np.concatenate(
                               [sys_p, r.integers(0, cfg.vocab, 40 + 13 * rid)])))
    for _ in range(8):
        srv.step()
    srv.submit(Request(rid=2, max_new=6, priority=0,
                       tokens=r.integers(0, cfg.vocab, 100)))
    srv.run()
    assert srv.stats.preemptions >= 1, "swap-out path never fired"
    return [list(map(int, srv.done[i].output)) for i in sorted(srv.done)]


def drive_session(mesh):
    srv = SlotServer(build(mesh, "packkv", "xla", "paged", session_cache=True))
    r = np.random.default_rng(0)
    for rid in range(2):
        srv.submit(Request(rid=rid, max_new=6,
                           tokens=r.integers(0, cfg.vocab, 150 + rid * 40)))
    srv.run()
    outs = [list(map(int, srv.done[i].output)) for i in range(2)]
    for rid in range(2):
        d = srv.done[rid]
        trace = np.concatenate([np.asarray(d.tokens), np.asarray(d.output),
                                r.integers(0, cfg.vocab, 8)])
        srv.submit(Request(rid=10 + rid, max_new=6, tokens=trace))
    srv.run()
    assert srv.stats.session_hits == 2, "returning sessions missed"
    return outs + [list(map(int, srv.done[10 + i].output)) for i in range(2)]


def drive(mesh):
    kind = scn["kind"]
    if kind == "preempt":
        return drive_preempt(mesh)
    if kind == "session":
        return drive_session(mesh)
    return drive_plain(mesh, scn["policy"], scn["backend"], scn["mode"],
                       scn.get("spec", False))


ref = drive((1, 1))
diverged = [list(ms) for ms in scn["meshes"] if drive(ms) != ref]
print("RESULT " + json.dumps({"diverged": diverged, "ref": ref}))
"""


def _run_child(scenario):
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, json.dumps(scenario)],
        capture_output=True, text=True,
        env=cpu_child_env(),
        cwd=".", timeout=900,
    )
    lines = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    assert lines, f"child failed:\n{r.stderr[-2000:]}"
    res = json.loads(lines[0][7:])
    assert not res["diverged"], \
        f"sharded output != single-device at meshes {res['diverged']}"
    assert res["ref"], "child produced no outputs"


@pytest.mark.slow
def test_sharded_paged_exact_all_mesh_shapes():
    """The tentpole case — packkv paged serving — over every supported
    shard count: kv in {2, 4} (head-sharded pool), dp=2 alone (row
    partition only) and the 2x2 composition."""
    _run_child({"kind": "plain", "policy": "packkv", "backend": "xla",
                "mode": "paged",
                "meshes": [[1, 2], [1, 4], [2, 1], [2, 2]]})


MATRIX = [
    # pallas paged kernels run inside the lane on local head slices
    {"kind": "plain", "policy": "packkv", "backend": "pallas",
     "mode": "paged", "meshes": [[1, 2]]},
    # uncompressed paged pool shards the same way
    {"kind": "plain", "policy": "none", "backend": "xla",
     "mode": "paged", "meshes": [[2, 2]]},
    # dense (non-paged) slot caches shard by head too
    {"kind": "plain", "policy": "packkv", "backend": "xla",
     "mode": "dense", "meshes": [[1, 2]]},
    # prefix-cache admission seeds per-slot perms through the lane
    {"kind": "plain", "policy": "packkv", "backend": "xla",
     "mode": "prefix", "meshes": [[2, 2]]},
    # speculative verify launches batch q_len=k+1 through the same lane
    {"kind": "plain", "policy": "packkv", "backend": "xla",
     "mode": "paged", "spec": True, "meshes": [[2, 2]]},
]


@pytest.mark.slow
@pytest.mark.parametrize(
    "scenario", MATRIX,
    ids=[f"{s['policy']}-{s['backend']}-{s['mode']}"
         + ("-spec" if s.get("spec") else "") for s in MATRIX])
def test_sharded_matrix_exact(scenario):
    _run_child(scenario)


@pytest.mark.slow
def test_sharded_preempt_resume_exact():
    """Swap-out gathers shard-local payloads into the same dense mini
    format as single-device, so the victim resumes bit-identically on the
    mesh."""
    _run_child({"kind": "preempt", "meshes": [[1, 2], [2, 2]]})


@pytest.mark.slow
def test_sharded_session_park_resume_exact():
    """Parked sessions cross the host boundary as full-head minis; the
    restore re-shards through the lane in_specs — hits stay exact."""
    _run_child({"kind": "session", "meshes": [[1, 2], [2, 2]]})


# -- in-process rejection / feature-off checks (single device) --------------

@pytest.fixture(scope="module")
def smoke_setup():
    cfg = SMOKES["llama2-7b"]
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _ecfg(mesh_shape):
    return EngineConfig(capacity=512, max_batch=2, calib_tokens=128,
                        bucket_unit=64, paged=True, page_size=128,
                        mesh_shape=mesh_shape)


def test_mesh_off_is_plain_engine(smoke_setup):
    cfg, params = smoke_setup
    eng = Engine(cfg, params, PackKVConfig(policy="packkv"), _ecfg((1, 1)))
    assert eng.mesh is None


def test_mesh_rejects_recurrent_family(smoke_setup):
    _, params = smoke_setup
    for arch in ("rwkv6-1.6b", "recurrentgemma-9b"):
        with pytest.raises(ValueError, match="--mesh"):
            Engine(SMOKES[arch], params, PackKVConfig(policy="none"),
                   EngineConfig(capacity=512, max_batch=2, calib_tokens=128,
                                mesh_shape=(1, 2)))


def test_mesh_rejects_indivisible_kv_heads(smoke_setup):
    cfg, params = smoke_setup  # n_kv_heads = 4
    with pytest.raises(ValueError, match="divisible"):
        Engine(cfg, params, PackKVConfig(policy="packkv"), _ecfg((1, 3)))


def test_mesh_rejects_nonpositive_shape(smoke_setup):
    cfg, params = smoke_setup
    with pytest.raises(ValueError, match="positive"):
        Engine(cfg, params, PackKVConfig(policy="packkv"), _ecfg((0, 2)))


def test_mesh_rejects_missing_devices(smoke_setup):
    cfg, params = smoke_setup  # 64x4 outsizes any test host's device count
    with pytest.raises(ValueError, match="devices"):
        Engine(cfg, params, PackKVConfig(policy="packkv"), _ecfg((64, 4)))
