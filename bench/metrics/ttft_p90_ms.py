"""90th percentile of the time to first token, from when each request
was due. Requests due in the window that have no token when it closes
count with the time they have waited so far."""
import numpy as np

UNIT = "ms"


def per_request(run) -> list[float]:
    out = []
    for r in run.recs:
        if not run.t0 <= r.due <= run.t1:
            continue
        t = r.req.t_first
        out.append(((t if t is not None and t <= run.t1 else run.t1)
                    - r.due) * 1e3)
    return out


def read(run):
    x = per_request(run)
    return float(np.percentile(x, 90)) if x else None
