"""Scheduler (``serving/engine.py`` ``SlotServer.step``): the mean over
the window's scheduler steps (``packkv.step`` spans) of the time the host
spent in each outside its waits for the device (``packkv.*.wait`` spans):
planning, dispatching, handing out tokens, retiring and admitting, which
the device waits on. A dispatch that blocks until earlier device work
is done counts here too. Importing this reader has ``bench.trace`` keep
the program's spans (``bench/program_spans.py``)."""
from bench import program_spans as ps

UNIT = "ms"
LAYER = "scheduler"
MOVES = "out_tok_s"

ps.install()


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window_ns
    host = ps.step_host_ns(ps.of(run.trace), lo, hi)
    return sum(host) / len(host) * 1e-6 if host else None
