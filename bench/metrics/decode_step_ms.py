"""Model step (``models/transformer.py`` ``decode_steps`` through
``Engine.decode_chunk``): device time of the decode programs in the
window per decode step they executed. The decode programs are the ones
the host waited for inside its decode dispatches."""
from bench import trace as tr

UNIT = "ms"
LAYER = "model step"
MOVES = "tpot_p90_ms"


def read(run):
    steps = run.delta("decode_steps")
    if run.trace is None or not steps:
        return None
    lo, hi = run.window_ns
    ns = tr.module_ns(run.trace, tr.waited_programs(run.trace, "bench.decode"),
                      lo, hi)
    return ns * 1e-6 / steps if ns else None
