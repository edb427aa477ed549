"""Trace one cell and price its decode launches by the paged kernel's grid.

    python bench/launch_fit.py --workload minitron-4b.long_decode \\
        --seed 7 --seconds 50 --out fit_minitron.json \\
        [--fixture trace_spans.json]

Runs the cell as ``bench/run.py --trace 1`` does, through the same
``run.measure``, and prints the same result line. It writes to ``--out``
what that line leaves out: the end-to-end metrics read off the traced
window (set beside an untraced run's, the cost of tracing), the window's
device idle time inside program spans, by span name, and one row
per decode launch in the window: its executed steps, the paged kernel's
live and dead grid steps (the launch's ``packkv.decode.emit`` span), its
program's device time and the kernel's device time inside it, with the
least-squares fit of kernel time per launch ≈ a·live + b·dead.
``--fixture`` writes a slice of the window around one admission, from a
little before its first prefill dispatch to a little after the next
decode dispatch (device operations and programs, harness and program
spans), the form of ``bench/tests/fixtures/trace_v5e_spans.json``.

Run on the chip only; pool the rows of several cells with ``fit``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DECODE = ("jit_packkv_decode_multi(*",)
MARGIN_NS = 20e6  # a fixture's slice reaches this far past its ends


def launch_rows(trace, lo: float, hi: float, kernels) -> list[dict]:
    """One row per ``packkv.decode.emit`` span in [lo, hi]: the launch the
    host had just waited for is the decode program that ended last before
    the span opened."""
    from bench import program_spans as ps
    from bench import trace as tr

    decode = [m for m in trace.modules if tr.match(m.name, DECODE)]
    kern = [e for e in trace.ops if tr.match(e.name, kernels)]
    rows = []
    for s in ps.of(trace):
        if s.name != "packkv.decode.emit" or not lo <= s.start <= hi:
            continue
        done = [m for m in decode if lo <= m.start and m.end <= s.start]
        if not done:
            continue
        m = max(done, key=lambda m: m.end)
        k_ns = sum(e.end - e.start for e in kern
                   if m.start <= e.start and e.end <= m.end)
        grid, dead = s.args["grid_steps"], s.args["dead_steps"]
        rows.append({"n_exec": s.args["n_exec"], "live": grid - dead,
                     "dead": dead, "program_ms": (m.end - m.start) * 1e-6,
                     "kernel_ms": k_ns * 1e-6})
    return rows


def _overlap_ns(a: list, b: list) -> float:
    """Time two sorted lists of disjoint intervals share."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_by_span(trace, lo: float, hi: float) -> dict:
    """Seconds of [lo, hi) with no device operation: in all, inside any
    program span, and inside the spans of each name (nested spans count
    for each of their names)."""
    from bench import program_spans as ps
    from bench import trace as tr

    gaps = tr.idle_gaps(trace, lo, hi)
    spans = ps.of(trace)
    inside = lambda evs: _overlap_ns(gaps, tr.union(tr.clip(evs, lo, hi)))
    names = sorted({s.name for s in spans})
    return {"idle_s": sum(b - a for a, b in gaps) * 1e-9,
            "in_program_span_s": inside(spans) * 1e-9,
            "by_name_s": {n[len(ps.PREFIX):]: inside(
                [s for s in spans if s.name == n]) * 1e-9 for n in names}}


def fit(rows: list[dict]) -> dict:
    """kernel_ms ≈ a·live + b·dead over the rows, least squares: a and b
    in us a grid step, the share of variance explained, and the largest
    residual as a share of its launch's kernel time."""
    import numpy as np

    x = np.array([[r["live"], r["dead"]] for r in rows], float)
    y = np.array([r["kernel_ms"] for r in rows], float)
    (a, b), *_ = np.linalg.lstsq(x, y, rcond=None)
    res = y - x @ np.array([a, b])
    ss = float(((y - y.mean()) ** 2).sum())
    return {"a_us": float(a) * 1e3, "b_us": float(b) * 1e3,
            "launches": len(rows),
            "r2": 1.0 - float((res ** 2).sum()) / ss if ss else None,
            "max_rel_residual": float(np.max(np.abs(res) / y))}


def fixture(trace, lo: float, hi: float) -> dict | None:
    """The slice of [lo, hi] around its first prefill dispatch, every
    event clipped to it (None if the window admitted nothing)."""
    from bench import program_spans as ps

    spans = ps.of(trace)
    pre = [s for s in spans if s.name == "packkv.prefill" and lo <= s.start]
    if not pre:
        return None
    nxt = [s for s in spans if s.name == "packkv.decode.dispatch"
           and s.start > pre[0].end and s.end <= hi]
    if not nxt:
        return None
    a, b = pre[0].start - MARGIN_NS, nxt[0].end + MARGIN_NS

    def cut(evs, extra=lambda e: []):
        return [[float(int(max(e.start, a))), float(int(min(e.end, b))),
                 e.name, *extra(e)] for e in evs if e.end > a and e.start < b]

    return {"ops": cut(trace.ops), "modules": cut(trace.modules),
            "spans": cut(trace.spans),
            "program_spans": cut(spans, lambda e: [e.args])}


def source(workload: str, kind: str, seed: int) -> str:
    """What a fixture written by ``fixture`` holds."""
    ms = MARGIN_NS * 1e-6
    return (f"{workload} on one TPU v5e ({kind}), seed {seed}: the traced "
            f"window from {ms:g} ms before its first prefill dispatch to "
            f"{ms:g} ms after the next decode dispatch, every event "
            "clipped to it")


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--fixture")
    args = ap.parse_args(argv)
    import jax

    from bench import serve, spec

    cell = spec.cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("launch_fit.py: runs on the chip only", file=sys.stderr)
        return 3
    peak = spec.peaks(dev.device_kind)
    from repro.utils import enable_compile_cache

    run.log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    kept = {}
    drive = serve.drive

    def keep(*a, **k):
        kept["run"], mem = drive(*a, **k)
        return kept["run"], mem

    serve.drive = keep
    res = run.measure(cell, args.seed, args.seconds, True, device, peak)
    print(json.dumps(res), flush=True)
    r = kept["run"]
    lo, hi = r.window_ns
    kernels = spec.reader("attn_us_per_grid_step").KERNELS
    rows = launch_rows(r.trace, lo, hi, kernels)
    out = {"workload": args.workload, "seed": args.seed,
           "end_to_end": {m.name: m.reader.read(r) for m in cell.metrics
                          if m.kind == "end_to_end"},
           "idle": idle_by_span(r.trace, lo, hi),
           "launches": rows, "fit": fit(rows) if len(rows) > 1 else None}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    if args.fixture:
        fx = fixture(r.trace, lo, hi)
        if fx is None:
            print("launch_fit.py: no admission in the window", file=sys.stderr)
            return 1
        fx["source"] = source(args.workload, device["kind"], args.seed)
        Path(args.fixture).write_text(json.dumps(fx))
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
