"""Serve minitron-4b at its published widths on one TPU chip, end to end.

The quickest proof that the serving main path still starts on the chip:
random bf16 weights from a seed, the paged packkv pool, ``Engine`` and
``SlotServer`` as ``repro.launch.serve`` drives them, and the Pallas
kernels compiled by Mosaic. The same requests are then served by the XLA
reference backend on the same chip and the two are compared.

    python chip_smoke.py               # one chip: pallas vs xla
    python chip_smoke.py --chips 4     # four chips: mesh (1, 4) vs (1, 1)
    python chip_smoke.py --smoke       # CPU rehearsal at the smoke config
    python chip_smoke.py --smoke --chips 4   # ... on 4 virtual CPU devices

Everything runs in this one process (a chip belongs to one process). With
no TPU it exits non-zero before printing a result; ``--smoke`` runs on the
CPU with the kernels interpreted and never claims a TPU. The last line of
standard output is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

SEED = 0
MAX_BATCH = 4
# The attention kernels alone, on the served cache: Pallas and XLA decode
# the same compressed bytes, both contract in f32, and differ only in f32
# summation order (~1e-7 relative). A misread page, tile or tier moves the
# output by that block's share of the softmax mass, 1e-3 and more; the
# bar leaves room for f32 exp and score rounding at large |score|.
ATTN_RTOL = 1e-4
# The first decode step's logits. The prefill is one shared XLA program,
# but 32 layers of random weights with a bf16 residual stream turn any
# difference in attention, even in summation order, into a few bf16 ulps
# of the logits: 1.7e-2 of max |logit| on a v5e, whether XLA rounded its
# attention operands to bf16 or not. This comparison therefore cannot see
# a kernel error below that (ATTN_RTOL does); it catches a wrong row,
# layer or program, which moves logits by their whole scale.
LOGIT_RTOL = 5e-2


def _sizes(smoke: bool) -> dict:
    if smoke:  # 2-layer smoke config: CPU rehearsal of the same path
        return dict(capacity=256, page=64, max_new=8,
                    prompts=(64, 128, 192), mesh_prompts=(64, 128))
    # one compile per distinct prompt length: three here, two in the
    # mesh phase (whose compiles are paid on four chips)
    return dict(capacity=2048, page=256, max_new=32,
                prompts=(256, 512, 1024), mesh_prompts=(256, 512))


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        _fail(msg)


def _requests(cfg, lengths, max_new):
    from repro.serving import Request

    rng = np.random.default_rng(SEED)
    return [Request(rid=i, max_new=max_new,
                    tokens=rng.integers(0, cfg.vocab, lengths[i % len(lengths)]))
            for i in range(8)]


def _engine(cfg, params, backend, sz, mesh=(1, 1), calibrate=True,
            width=None):
    """``width``: one tier of that width for K and for V (with
    ``calibrate=False``), in place of the default (2, 4, 8)."""
    from repro.core.cache import PackKVConfig
    from repro.serving import Engine, EngineConfig

    pack = PackKVConfig(policy="packkv")
    if width is not None:
        pack = dataclasses.replace(pack, k_tiers=(width,), k_fracs=(1.0,),
                                   v_tiers=(width,), v_fracs=(1.0,))
    t0 = time.perf_counter()
    eng = Engine(cfg, params, pack, EngineConfig(
        capacity=sz["capacity"], max_batch=MAX_BATCH, backend=backend,
        paged=True, page_size=sz["page"], bucketed=False,
        prefill_chunk_pages=max(sz["prompts"]) // sz["page"],
        mesh_shape=mesh, calibrate=calibrate))
    ks, vs = eng.pack_cfg.k_spec(cfg.hd), eng.pack_cfg.v_spec(cfg.hd)
    print(f"[{backend} mesh {mesh}] engine built in "
          f"{time.perf_counter() - t0:.1f}s (calibration compile included); "
          f"{'calibrated' if calibrate else 'static'} K tiers "
          f"{ks.widths}x{ks.counts}, V tiers "
          f"{vs.widths}x{vs.counts}; pool {eng.pack_cfg.pool_pages} pages "
          f"of {sz['page']} tokens")
    return eng


def _serve(eng, cfg, lengths, sz, tag):
    """Serve the requests once through a fresh ``SlotServer``; returns
    ({rid: tokens}, seconds, tokens delivered, the server's slot cache)."""
    from repro.serving import SlotServer

    srv = SlotServer(eng)
    for r in _requests(cfg, lengths, sz["max_new"]):
        srv.submit(r)
    t0 = time.perf_counter()
    done = srv.run()
    dt = time.perf_counter() - t0
    out = {r.rid: np.asarray(r.output) for r in done}
    _check(len(out) == 8, f"{tag}: {len(out)}/8 requests completed")
    for rid, toks in out.items():
        _check(len(toks) == sz["max_new"],
               f"{tag}: request {rid} produced {len(toks)} of "
               f"{sz['max_new']} tokens")
    return out, dt, srv.stats.tokens_out, srv.cache


def _first_step_logits(eng, cfg, sz):
    """Prefill one request per slot, then one decode step: (prefill
    logits [B, V], first decode logits [B, V], cache)."""
    import jax.numpy as jnp

    cache = eng.alloc_slot_cache()
    pre = []
    for slot, r in enumerate(_requests(cfg, sz["prompts"], sz["max_new"])
                             [:MAX_BATCH]):
        lg, cache = eng.insert_request(cache, slot, r.tokens)
        pre.append(np.asarray(lg))
    tok = np.argmax(np.stack(pre), -1).astype(np.int32)[:, None]
    logits, cache = eng.decode(cache, jnp.asarray(tok))
    return np.stack(pre), np.asarray(logits), cache


def _attention_gap(cache, cfg, layer) -> float:
    """max |pallas - xla| / max |xla| of one layer's decode attention over
    the live paged cache, for one random query per slot."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import paged_decode_attention

    cache_l = jax.tree.map(lambda x: x[layer], cache)
    q = jax.random.normal(jax.random.PRNGKey(SEED),
                          (MAX_BATCH, cfg.n_heads, cfg.hd), jnp.float32)
    out = {b: np.asarray(jax.jit(lambda q, c, b=b: paged_decode_attention(
               q, c, cfg.hd ** -0.5, backend=b))(q, cache_l))
           for b in ("pallas", "xla")}
    return float(np.max(np.abs(out["pallas"] - out["xla"]))
                 / np.max(np.abs(out["xla"])))


def _check_attention(cache, cfg) -> None:
    for layer in (0, cfg.n_layers - 1):
        gap = _attention_gap(cache, cfg, layer)
        print(f"layer {layer} decode attention on the served cache: pallas "
              f"vs xla max |d| = {gap:.3e} of max |out| (tolerance "
              f"{ATTN_RTOL})")
        _check(gap <= ATTN_RTOL,
               f"layer {layer} attention: pallas vs xla {gap:.3e}")


def _decode_rate(eng, cache, logits, reps=4):
    """Warm decode tokens/s of the serving decode program at full batch."""
    import jax.numpy as jnp

    tok = jnp.asarray(np.argmax(logits, -1).astype(np.int32)[:, None])
    active = np.ones(MAX_BATCH, bool)
    t_max = eng.ecfg.decode_chunk
    toks, _, cache = eng.decode_chunk(cache, tok, active, t_max, None)
    t0 = time.perf_counter()
    for _ in range(reps):
        toks, n, cache = eng.decode_chunk(
            cache, jnp.asarray(toks[-1][:, None]), active, t_max, None)
    return reps * t_max * MAX_BATCH / (time.perf_counter() - t0)


def _device_bytes(tree, device) -> int:
    import jax

    return sum(s.data.nbytes for x in jax.tree.leaves(tree)
               for s in x.addressable_shards if s.device == device)


def _memory(device) -> str:
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return (f"{stats['bytes_in_use'] / 2**30:.2f} GiB in use, peak "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")


def _init_params(api, cfg, sharding=None):
    """bf16 params from a seed. One jitted program, so each leaf's f32
    normal draws fuse into its bf16 cast instead of materialising."""
    import jax

    t0 = time.perf_counter()
    init = jax.jit(lambda k: api.init(k, cfg), out_shardings=sharding)
    params = jax.block_until_ready(init(jax.random.PRNGKey(SEED)))
    n = sum(x.size for x in jax.tree.leaves(params))
    b = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"params: {n / 1e9:.2f}e9 ({b / 1e9:.2f} GB bf16) in "
          f"{time.perf_counter() - t0:.1f}s")
    return params


def one_chip(cfg, api, sz, dev, where) -> None:
    params = _init_params(api, cfg)
    res = {}
    for backend in ("pallas", "xla"):
        eng = _engine(cfg, params, backend, sz)
        # each return drops its server's slot cache (0.45 GB at full size)
        out, t_cold = _serve(eng, cfg, sz["prompts"], sz, backend)[:2]
        again, t_warm, n_tok = _serve(eng, cfg, sz["prompts"], sz, backend)[:3]
        _check(all(np.array_equal(out[i], again[i]) for i in out),
               f"{backend}: a second run changed the outputs")
        pre, logits, cache = _first_step_logits(eng, cfg, sz)
        _check(np.isfinite(pre).all() and np.isfinite(logits).all(),
               f"{backend}: non-finite logits")
        if backend == "pallas":
            _check_attention(cache, cfg)
        rate = _decode_rate(eng, cache, logits)
        print(f"[{backend}] 8 requests served: cold {t_cold:.1f}s (compiles "
              f"included), warm {t_warm:.2f}s = {n_tok / t_warm:.1f} tok/s "
              f"(admission + decode) on {where}; decode "
              f"{rate:.1f} tok/s at batch {MAX_BATCH} on {where}")
        res[backend] = dict(out=out, pre=pre, logits=logits)
        del eng, cache
        gc.collect()
        print(f"[{backend}] device 0 memory: {_memory(dev)}")

    # calibration on random weights keeps one 4-bit tier (the field-major
    # decode); the default (2, 4, 8)-bit tiers put the narrowest payloads
    # through the lane-repeat decode, one tier of each other width through
    # the field-major decode at that width
    for width in (None, 1, 2, 8, 16):
        eng = _engine(cfg, params, "pallas", sz, calibrate=False, width=width)
        _check_attention(_first_step_logits(eng, cfg, sz)[2], cfg)
        del eng
        gc.collect()

    p, x = res["pallas"], res["xla"]
    _check(np.array_equal(p["pre"], x["pre"]),
           "prefill logits differ (one shared XLA program on both backends)")
    same = np.mean([np.mean(p["out"][i] == x["out"][i]) for i in p["out"]])
    diff = float(np.max(np.abs(p["logits"] - x["logits"])))
    scale = float(np.max(np.abs(x["logits"])))
    print(f"pallas vs xla: {same:.4f} of greedy tokens identical; first "
          f"decode step max |dlogit| {diff:.3e} = {diff / scale:.3e} of max "
          f"|logit| {scale:.3f} (tolerance {LOGIT_RTOL})")
    _check(diff <= LOGIT_RTOL * scale,
           f"pallas vs xla first-step logits differ by {diff:.3e} > "
           f"{LOGIT_RTOL} x {scale:.3f}")


def four_chips(cfg, api, sz, devs, where) -> None:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    res = {}
    for mesh in ((1, 1), (1, 4)):
        sharding = None
        if mesh != (1, 1):
            # the engine replicates params over its mesh; made replicated
            # from the start, no second single-device copy sits on device 0
            m = Mesh(np.array(devs[:4]).reshape(mesh), ("dp", "kv"))
            sharding = NamedSharding(m, PartitionSpec())
        params = _init_params(api, cfg, sharding)
        eng = _engine(cfg, params, "pallas", sz, mesh)
        if eng.mesh is not None:
            ids = sorted({d.id for d in eng.mesh.devices.flat})
            _check(len(ids) == 4, f"mesh spans devices {ids}, not 4 chips")
            print(f"mesh {mesh} devices: {list(eng.mesh.devices.flat)}")
        out, t_cold, _, cache = _serve(eng, cfg, sz["mesh_prompts"], sz,
                                       f"mesh {mesh}")
        res[mesh] = dict(out=out, dev0=_device_bytes(cache, devs[0]),
                         total=sum(x.nbytes for x in jax.tree.leaves(cache)))
        print(f"[mesh {mesh}] 8 requests in {t_cold:.1f}s (compiles included)"
              f" on {where}; slot cache {res[mesh]['total']:,} bytes, "
              f"{res[mesh]['dev0']:,} resident on device 0")
        del eng, cache, params
        gc.collect()
        print(f"[mesh {mesh}] freed; device 0 memory: {_memory(devs[0])}")

    a, b = res[(1, 1)], res[(1, 4)]
    _check(all(np.array_equal(a["out"][i], b["out"][i]) for i in a["out"]),
           "mesh (1, 4) greedy outputs differ from (1, 1)")
    print(f"mesh (1, 4) outputs bit-identical to (1, 1); device 0 cache "
          f"bytes {b['dev0']:,} / {a['dev0']:,} = {b['dev0'] / a['dev0']:.3f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the KV-head mesh phase, (1, 4) vs (1, 1)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal at the smoke config (kernels "
                    "interpreted); never reports a TPU")
    args = ap.parse_args()
    if args.smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                f"{os.environ.get('XLA_FLAGS', '')} "
                f"--xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax

    from repro.configs import get_arch
    from repro.models import get_model
    from repro.utils import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    print(f"devices: {len(devs)} x {dev.platform} {dev.device_kind!r}: "
          f"{devs}")
    if not args.smoke and dev.platform != "tpu":
        _fail(f"no TPU found (platform {dev.platform!r}); use --smoke for "
              "the CPU rehearsal")
    _check(len(devs) >= args.chips,
           f"--chips {args.chips} needs {args.chips} devices, have {len(devs)}")
    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_arch("minitron-4b", smoke=args.smoke)
    if args.smoke and args.chips == 4:  # the smoke config has 2 kv heads
        cfg = dataclasses.replace(cfg, n_heads=8, n_kv_heads=4)
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} q / {cfg.n_kv_heads} kv heads x {cfg.hd}, vocab "
          f"{cfg.vocab}; policy packkv, paged pool, backend pallas "
          f"({'interpreted' if dev.platform == 'cpu' else 'compiled'})")
    sz = _sizes(args.smoke)
    where = f"{dev.platform} {dev.device_kind}"
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(cfg, get_model(cfg), sz, devs, where)
    else:
        one_chip(cfg, get_model(cfg), sz, dev, where)
    print(f"total {time.perf_counter() - t0:.1f}s; device 0 memory: "
          f"{_memory(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
