"""Output tokens delivered in the window per second of the window."""
UNIT = "tokens/s"


def read(run):
    n = sum(1 for r in run.recs for t in r.req.token_times
            if run.t0 <= t <= run.t1)
    return n / run.window_s
