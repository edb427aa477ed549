"""Device: the share of the traced window in which no operation ran."""
from bench import trace as tr

UNIT = "fraction"
LAYER = "device"
MOVES = "out_tok_s"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window_ns
    return 1.0 - tr.busy_ns(run.trace, lo, hi) / (hi - lo)
