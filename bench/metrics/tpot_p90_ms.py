"""90th percentile over requests of the time per output token.

A multi-step launch stamps all of its tokens with one time, so a
request's time per token is taken between its first and last stamps in
the window, over the tokens that came after the first stamp. Requests
with fewer than two stamps in the window have no such time."""
import numpy as np

UNIT = "ms"


def per_request(run) -> list[float]:
    out = []
    for r in run.recs:
        ts = [t for t in r.req.token_times if run.t0 <= t <= run.t1]
        if len(set(ts)) < 2:
            continue
        first = ts.count(ts[0])
        out.append((ts[-1] - ts[0]) / (len(ts) - first) * 1e3)
    return out


def read(run):
    x = per_request(run)
    return float(np.percentile(x, 90)) if x else None
