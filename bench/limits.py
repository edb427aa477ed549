"""Readings that a cell's correctness limits are set from.

    python bench/limits.py --workload minitron-4b.long_decode \\
        --seeds 101,102,103 --control-seeds 104,105,106 --seconds 50 \\
        --prompt-seeds 107,108,109

Each reading of ``--seeds`` and ``--control-seeds`` is one run of the
cell through ``bench/run.py``'s own ``measure``, and so through its
comparison. For each of ``--seeds`` the program runs as the
configuration states, and the same reference pass also reads the
reference one precision step below bfloat16 (weights and activations of
every matmul in int8, and in fp8 e4m3) on both numbers. For each of
``--control-seeds`` the program runs with the configuration's
``control`` PackKV settings, the cache one precision step below the one
it states. For each of ``--prompt-seeds`` only the reference runs, on
the prompts of the traffic's first requests (those that fill the slots
before the window), and reads how far the lower precisions' logits at
the last prompt position lie from its own: the controls of
``prefill_logit_err``, which needs no served token. One JSON line per
reading.

Runs in one process, on the chip; ``--smoke`` rehearses it on the CPU at
smoke sizes (no number from that is a device reading).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def prompt_reading(cell, seed: int) -> dict:
    from bench import correct, spec
    from bench import traffic as tf

    cfg, mix = cell.config, cell.traffic
    ref = spec.reference(cfg["reference"])
    stream = tf.cards(mix)
    seqs = [(tf.prompt_tokens(seed, rid, next(stream).prompt_len,
                              cfg["model"]["vocab"]), np.zeros(1, np.int32))
            for rid in range(cfg["check"]["sample_requests"])]
    gaps = ref.served_gaps(seed, cfg["model"], seqs,
                           max(len(p) for p, _ in seqs) + 1,
                           kv=correct.kv_of(cfg), lowp=ref.LOWER,
                           bf16=cfg["dtype"] == "bfloat16")
    return {f"{m}_prefill_logit_err": max(
        correct.rel_err(g[m + "_first"], g["first"]) for g in gaps)
        for m in ref.LOWER}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--prompt-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import run, smoke, spec

    cell = spec.cell(args.workload)
    dev = jax.devices()[0]
    if args.smoke:
        cell = smoke.shrink(cell)
    elif dev.platform != "tpu":
        print("limits.py: no TPU; use --smoke for a CPU rehearsal",
              file=sys.stderr)
        return 3
    else:
        from repro.utils import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    control = cell.config["control"]["packkv"]
    seeds = lambda s: [int(x) for x in s.split(",") if x]
    for seed in seeds(args.prompt_seeds):
        print(json.dumps({"seed": seed, "kind": "prompts",
                          **prompt_reading(cell, seed)}), flush=True)
    plan = ([(s, "program") for s in seeds(args.seeds)]
            + [(s, "kv_control") for s in seeds(args.control_seeds)])
    for seed, kind in plan:
        res = run.measure(cell, seed, args.seconds, False, device, None,
                          packkv=control if kind == "kv_control" else None,
                          lower=kind == "program")
        print(json.dumps({"seed": seed, "kind": kind,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
