"""Run one benchmark cell once, on the chip, and print one result line.

    python bench/run.py --workload minitron-4b.long_decode --seed 7 \\
        --seconds 40 --trace 0

The cell (``BENCHMARK.json`` -> ``workloads``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``). The run
builds the model's weights on the device from ``--seed``, warms every
program the traffic can reach, loads the server for ``--seconds``, checks
what it served against the plain reference, and prints as its last line
of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its limit.

It runs in this one process (a chip belongs to one process) and exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for. JAX's persistent compilation cache is kept inside the
checkout (``repro.utils.enable_compile_cache``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(msg, flush=True)


def attempted_failed(run) -> tuple[int, int]:
    """Requests live in the window (sent in it, or in flight when it
    opened), and those of them that ended in another state than done."""
    live = [r for r in run.recs if r.sent >= run.t0 or
            r.done_at is None or r.done_at >= run.t0]
    bad = [r for r in live if r.req.status not in ("done", "active", "queued")]
    return len(live), len(bad)


def measure(cell, seed: int, seconds: float, trace: bool, device: dict,
            peak: dict | None, packkv: dict | None = None,
            lower: bool = False) -> dict:
    """Drive the cell and check it; the result object. ``peak`` is None
    off the chip, and then no metric is reported. ``packkv`` runs the
    program with other cache settings (the control) against the same
    reference; ``lower`` adds the readings of the reference one precision
    step below the configuration's (int8 and fp8 matmuls)."""
    from bench import correct, serve, spec
    from bench import trace as tr

    ref = spec.reference(cell.config["reference"])
    run, mem = serve.drive(cell.config, cell.traffic, seed, seconds, ref,
                           t_start=T_START, trace=trace, log=log,
                           packkv=packkv)
    run.peak = peak
    log(f"window: {run.window_s:.3f}s; setup {run.t0 - run.t_start:.3f}s; "
        f"programs built or loaded inside the window: "
        f"{run.compiles_in_window}")
    if run.lateness:
        late = sorted(run.lateness)
        log(f"load generator late by: median {late[len(late) // 2] * 1e3:.1f}"
            f" ms, max {late[-1] * 1e3:.1f} ms over {len(late)} sends")
    log(f"decode steps {run.delta('decode_steps')}, launches "
        f"{run.delta('chunk_launches')}, tokens out {run.delta('tokens_out')},"
        f" admitted {run.delta('admitted')}, completed "
        f"{run.delta('completed')}, prefill chunks "
        f"{run.delta('prefill_chunks')}")
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics:
        if m.kind != kind or peak is None:
            continue
        v = m.reader.read(run)
        if v is None and kind == "end_to_end":
            raise RuntimeError(f"end-to-end metric {m.name} read nothing")
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = dict(device, memory_peak_bytes=mem)
    out = {}
    if trace:
        lo, hi = run.window_ns
        device["busy_s"] = tr.busy_ns(run.trace, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = tr.breakdown(run.trace, lo, hi)
    t = time.perf_counter()
    checks = correct.check(run, cell.config, cell.traffic, seed, ref,
                           control=lower)
    log(f"reference check: {time.perf_counter() - t:.1f}s")
    attempted, failed = attempted_failed(run)
    res = {"correct": correct.passed(checks), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    res.update(out)
    res["checks"] = checks
    for name, d in checks.items():
        lim = (f"<= {d['max']}" if "max" in d else
               f">= {d['min']}" if "min" in d else "(read only)")
        print(f"check {name}: {d['value']} {lim}", file=sys.stderr)
    return res


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("run.py: --seed must be >= 0", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import repro  # noqa: F401  (the system under test must be here)
    from bench import spec

    cell = spec.cell(args.workload)
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"run.py: no TPU (JAX found {dev.platform!r}); the benchmark "
              "runs on the chip only", file=sys.stderr)
        return 3
    if len(devs) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX finds "
              f"{len(devs)}", file=sys.stderr)
        return 3
    peak = spec.peaks(dev.device_kind)
    from repro.utils import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log(f"device: {device}")
    res = measure(cell, args.seed, args.seconds, bool(args.trace), device,
                  peak)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
