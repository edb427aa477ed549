"""Serving launcher: calibrated PackKV engine + slot-scheduled requests.

Every family (transformer, rwkv6, hybrid_rglru) serves through the one
chunk-interleaved ``SlotServer`` engine.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
      --requests 12 --max-new 32 --policy packkv
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from ..configs import get_arch
from ..core.cache import PackKVConfig
from ..models import get_model
from ..serving import Engine, EngineConfig, Request, SlotServer
from ..utils import enable_compile_cache, tree_bytes


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=192)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=1024)
    ap.add_argument("--policy", default="packkv", choices=["packkv", "none", "kivi"])
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--prefill-chunk-pages", type=int, default=1,
                    help="admission chunk budget in pages per scheduler "
                    "step; decode never stalls more than one chunk "
                    "(0 = legacy monolithic prefill; docs/serving.md)")
    ap.add_argument("--paged", action="store_true",
                    help="paged compressed region: shared page pool + "
                    "page-reservation admission (docs/architecture.md)")
    ap.add_argument("--page-size", type=int, default=256)
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="pool size in pages; < batch*capacity/page_size "
                    "oversubscribes (admission blocks on reservations)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-prefix page cache: refcounted page reuse "
                    "across requests + suffix-only prefill (requires "
                    "--paged; docs/serving.md)")
    ap.add_argument("--prefix-cache-pages", type=int, default=None,
                    help="max pool pages the prefix index may pin "
                    "(default unbounded; pool pressure still evicts LRU)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="self-speculative decode: host n-gram drafting + "
                    "batched k-token verify launches; greedy outputs stay "
                    "bit-identical (docs/serving.md)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max drafted tokens per verify launch "
                    "(window = k + 1)")
    ap.add_argument("--preempt", action="store_true",
                    help="priority preemption: a blocked higher-class "
                    "admission swaps a lower-class victim's compressed "
                    "pages to host RAM; the victim resumes bit-identically "
                    "later (docs/serving.md)")
    ap.add_argument("--session-cache", action="store_true",
                    help="multi-turn session cache: retiring slots park "
                    "their compressed pages host-side; a returning session "
                    "restores them and prefills only its new suffix "
                    "(docs/serving.md)")
    ap.add_argument("--session-cache-mb", type=int, default=256,
                    help="host-RAM budget for parked sessions in MB "
                    "(LRU-by-bytes beyond it: spill to --session-disk-dir "
                    "or drop)")
    ap.add_argument("--session-ttl-s", type=float, default=None,
                    help="idle parked sessions expire after this many "
                    "seconds (default: never)")
    ap.add_argument("--session-disk-dir", default=None, metavar="DIR",
                    help="disk spill tier for LRU host-tier victims "
                    "(savable-dtype mini serializers; default: drop)")
    ap.add_argument("--priority-every", type=int, default=0, metavar="N",
                    help="demo traffic shaping: every Nth request is "
                    "class 0 (highest), the rest class 1 (0 = all class 0)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; expired requests retire "
                    "with their partial output at the next scheduler step")
    ap.add_argument("--aging-steps", type=int, default=32,
                    help="scheduler steps per one class promotion of "
                    "queued work (0 = strict priority, may starve)")
    ap.add_argument("--stats-json", default=None, metavar="PATH",
                    help="dump final SlotStats (incl. drafted/accepted "
                    "counts and acceptance rate) as JSON to PATH")
    ap.add_argument("--mesh", default="1,1", metavar="DP,KV",
                    help="serving mesh shape 'dp,kv': shard pool payloads "
                    "by KV head over kv devices and partition attention "
                    "rows over dp (1,1 = single-device; outputs are "
                    "bit-identical either way; docs/serving.md). Needs "
                    "dp*kv visible devices — on CPU set "
                    "XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT first")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    try:
        mesh_shape = tuple(int(x) for x in args.mesh.split(","))
        assert len(mesh_shape) == 2
    except (ValueError, AssertionError):
        raise SystemExit(f"--mesh takes 'dp,kv' (e.g. 1,2), got {args.mesh!r}")

    dev = jax.devices()[0]
    where = f"{dev.platform} {dev.device_kind}"
    print(f"devices: {len(jax.devices())} x {where}; compile cache "
          f"{enable_compile_cache()}")
    cfg = get_arch(args.arch, smoke=args.smoke)
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only; nothing to serve")
    api = get_model(cfg)
    if args.session_cache and api.evacuate_slot is None:
        raise SystemExit(
            f"{args.arch} (family {cfg.family!r}) cannot serve "
            "--session-cache: its recurrent slot state has no "
            "evacuate/restore ops to park through — drop --session-cache")
    key = jax.random.PRNGKey(args.seed)
    params = api.init(key, cfg)

    pack = PackKVConfig(policy=args.policy)
    ecfg = EngineConfig(capacity=args.capacity, max_batch=args.batch,
                        backend=args.backend, paged=args.paged,
                        page_size=args.page_size, pool_pages=args.pool_pages,
                        prefix_cache=args.prefix_cache,
                        prefix_cache_pages=args.prefix_cache_pages,
                        prefill_chunk_pages=args.prefill_chunk_pages,
                        spec_decode=args.spec_decode, spec_k=args.spec_k,
                        preempt=args.preempt, aging_steps=args.aging_steps,
                        session_cache=args.session_cache,
                        session_cache_mb=args.session_cache_mb,
                        session_ttl_s=args.session_ttl_s,
                        session_disk_dir=args.session_disk_dir,
                        mesh_shape=mesh_shape)
    t0 = time.time()
    engine = Engine(cfg, params, pack, ecfg)
    print(f"engine built in {time.time() - t0:.1f}s; policy={args.policy}")
    if engine.mesh is not None:
        print(f"serving mesh dp={mesh_shape[0]} x kv={mesh_shape[1]} over "
              f"{mesh_shape[0] * mesh_shape[1]} devices: pool payloads "
              f"sharded by KV head ({cfg.n_kv_heads} -> "
              f"{cfg.n_kv_heads // mesh_shape[1]}/shard), page ledger "
              "replicated")
    ks, vs = engine.pack_cfg.k_spec_static, engine.pack_cfg.v_spec_static
    if args.policy == "packkv" and ks is not None:  # recurrent: no KV tiers
        print(f"calibrated K tiers {ks.widths}×{ks.counts}; "
              f"V tiers {vs.widths}×{vs.counts}")

    if cfg.input_mode != "tokens":
        raise SystemExit(
            f"{args.arch} takes input_mode {cfg.input_mode!r}; the request "
            "queue carries token prompts only — batch such inputs through "
            "Engine.generate instead")
    server = SlotServer(engine)
    rng = np.random.default_rng(args.seed)
    # --prefix-cache demo traffic: every request opens with the same
    # two-page "system prompt" so later admissions hit the index
    sys_prompt = (rng.integers(0, cfg.vocab, 2 * args.page_size)
                  if args.prefix_cache else np.zeros(0, np.int64))
    for rid in range(args.requests):
        plen = int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        toks = np.concatenate([sys_prompt, rng.integers(0, cfg.vocab, plen)])
        n = args.priority_every
        prio = 0 if (n <= 0 or rid % n == 0) else 1
        server.submit(Request(rid=rid, max_new=args.max_new, tokens=toks,
                              priority=prio, deadline_ms=args.deadline_ms))
    t0 = time.time()
    done = server.run()
    n_tok = sum(len(r.output) for r in done)
    dt = time.time() - t0
    print(f"{args.requests} requests, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok / dt:.1f} tok/s on {where})")
    if args.session_cache:
        # returning-session demo: every request comes back with a short
        # follow-up on its full first-turn trace -> served from the park
        t1 = time.time()
        for rid in range(args.requests):
            r = server.done[rid]
            trace = np.concatenate([np.asarray(r.tokens),
                                    np.asarray(r.output)])
            ext = rng.integers(0, cfg.vocab, 8)
            server.submit(Request(rid=args.requests + rid,
                                  max_new=args.max_new,
                                  tokens=np.concatenate([trace, ext])))
        n2 = sum(len(r.output) for r in server.run())
        print(f"{args.requests} returning sessions, {n2} tokens in "
              f"{time.time() - t1:.1f}s")
    s = server.stats
    print(f"slot scheduler: {s.decode_steps} decode steps, "
          f"occupancy {s.occupancy:.2f}, {s.slot_reuses} slot reuses, "
          f"{s.admitted} admitted / {s.completed} completed, "
          f"{s.prefill_chunks} prefill chunks")
    if args.paged:
        print(f"paged pool: {engine.pack_cfg.pool_pages} pages of "
              f"{args.page_size} tokens, peak reserved "
              f"{s.pages_reserved_peak}, {s.admission_blocks} "
              f"admission blocks")
    if args.prefix_cache:
        print(f"prefix cache: {s.prefix_hits}/{s.prefix_lookups} hits "
              f"(rate {s.prefix_hit_rate:.2f}), "
              f"{s.prefix_pages_shared} pages shared by reference, "
              f"{s.prefix_evictions} evictions")
    if args.spec_decode:
        print(f"speculative decode: {s.spec_launches} verify launches, "
              f"{s.spec_accepted}/{s.spec_drafted} drafts accepted "
              f"(rate {s.acceptance_rate:.2f})"
              + (f", {s.degraded_steps} degraded steps (spec disabled by "
                 "the straggler watchdog)" if s.degraded_steps else ""))
    if args.preempt:
        print(f"preemption: {s.preemptions} swap-outs "
              f"({s.swapped_pages} pages out / {s.restored_pages} back)")
    if args.session_cache:
        st = server._sessions
        print(f"session cache: {s.session_parks} parks, "
              f"{s.session_hits}/{s.session_lookups} hits "
              f"(rate {s.session_hit_rate:.2f}), "
              f"{s.session_restored_pages} pages restored, "
              f"{s.session_evictions} evicted/expired; host "
              f"{st.nbytes / 1e6:.1f} MB resident "
              f"(peak {st.peak_bytes / 1e6:.1f}), "
              f"{st.spills} disk spills / {st.loads} loads")
    if s.cancelled or s.expired:
        print(f"retired early: {s.cancelled} cancelled, "
              f"{s.expired} past deadline")
    if args.stats_json:
        with open(args.stats_json, "w") as f:
            json.dump(s.to_json(), f, indent=2, default=float)
        print(f"wrote {args.stats_json}")

    # cache memory report (the paper's deliverable). Byte counts are
    # static-shape-determined, so the allocated slot cache suffices — and
    # unlike a whole-batch prefill it is valid for oversubscribed pools.
    cap = args.capacity
    comp_bytes = tree_bytes(engine.alloc_slot_cache())
    raw = (cfg.n_layers * 2 * args.batch * cfg.n_kv_heads * cap * cfg.hd * 2)
    print(f"cache pytree bytes (capacity {cap}): {comp_bytes:,} "
          f"vs raw bf16 {raw:,} -> {raw / comp_bytes:.2f}x smaller")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
