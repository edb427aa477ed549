"""Find an open-loop cell's knee: the highest arrival rate at which the
queue of requests waiting for admission does not grow over a window.

    python bench/knee_sweep.py --config minitron-4b --traffic chat \\
        --rates 1.0,1.5,2.0,2.5,3.0 --seconds 40 --seed 5

Each rate runs the open-loop traffic mix at that rate through a fresh engine
and server, exactly as ``bench/run.py`` drives it, and prints one JSON
line: requests sent and finished in the window, how many waited for a
slot when the window opened and when it closed, output tokens per second
and the 90th percentile time to first token. A rate is sustained when the
waiting count grows by at most ``--slack`` requests over the window. The
last line names the knee; the traffic file records the sweep and sets
its rate at a share of that knee (the mix need not be in a cell yet).
Runs in one process, on the chip; ``--smoke`` rehearses it on the CPU at
smoke sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def waiting(run, t: float) -> int:
    """Requests due by ``t`` that had not been admitted (no first token)."""
    return sum(1 for r in run.recs if r.due <= t and
               (r.req.t_first is None or r.req.t_first > t))


def one_rate(cell, rate: float, seed: int, seconds: float, slack: int) -> dict:
    from bench import serve, spec

    mix = dict(cell.traffic, rate_per_s=rate)
    ref = spec.reference(cell.config["reference"])
    run, _ = serve.drive(cell.config, mix, seed, seconds, ref,
                         t_start=time.perf_counter(), log=lambda m: None)
    sent = sum(1 for r in run.recs if run.t0 <= r.due <= run.t1)
    done = sum(1 for r in run.recs if r.done_at is not None
               and run.t0 <= r.done_at <= run.t1)
    w0, w1 = waiting(run, run.t0), waiting(run, run.t1)
    return {"rate_per_s": rate, "sent": sent, "finished": done,
            "waiting_at_open": w0, "waiting_at_close": w1,
            "sustained": w1 - w0 <= slack,
            "out_tok_s": spec.reader("out_tok_s").read(run),
            "ttft_p90_ms": spec.reader("ttft_p90_ms").read(run),
            "tpot_p90_ms": spec.reader("tpot_p90_ms").read(run)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--slack", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import smoke, spec

    cell = spec.Cell(f"{args.config}.{args.traffic}", 1,
                     spec.config(args.config), spec.traffic(args.traffic), [])
    if cell.traffic["loop"] != "open":
        print("knee_sweep.py: the mix's loop is not open", file=sys.stderr)
        return 2
    if args.smoke:
        cell = smoke.shrink(cell)
    elif jax.devices()[0].platform != "tpu":
        print("knee_sweep.py: no TPU; use --smoke for a CPU rehearsal",
              file=sys.stderr)
        return 3
    else:
        from repro.utils import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        res = one_rate(cell, rate, args.seed, args.seconds, args.slack)
        print(json.dumps(res), flush=True)
        if res["sustained"]:
            knee = rate
    print(json.dumps({"knee_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
