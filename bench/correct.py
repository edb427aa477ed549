"""The comparison that decides ``correct``.

Once the window has closed and the program is freed, a sample of the
requests the window finished, drawn from the seed and always holding the
longest, is run through the configuration's plain reference with the
tokens the program served. Two numbers are compared:

* ``logit_gap``: the widest gap by which a served token's reference
  logit lies below the reference's best (greedy serving; 0 for a program
  that matches the reference exactly). It covers decode over the
  compressed cache, and fails a cache kept coarser than stated.
* ``prefill_logit_err``: the largest relative distance, over the sampled
  requests, between the logits that the program's admission produced for
  the last prompt token (they pick the first served token) and the
  reference's, ``|program - reference| / |reference|`` over the whole
  vocabulary. It covers chunked prefill, every matmul and the head at the
  precision the configuration states, and fails a lower one.

Their limits live in the configuration file; ``PERF.md`` gives the
readings they were set from. Every finished request must also hold
exactly the tokens it asked for (the traffic sends no end-of-sequence
token).
"""
from __future__ import annotations

import numpy as np


def finished(run) -> list:
    """Requests the window finished, in the order they were sent."""
    return [r for r in run.recs if r.done_at is not None
            and run.t0 <= r.done_at <= run.t1 and r.req.status == "done"]


def sample(run, seed: int, tokens: int, most: int) -> list:
    """The longest finished request (prompt plus output), then others in
    a seeded order until the sample holds ``tokens`` served tokens or
    ``most`` requests."""
    done = finished(run)
    if not done:
        return []
    size = lambda r: len(r.req.tokens) + len(r.req.output)
    longest = max(done, key=lambda r: (size(r), -r.rid))
    rest = [r for r in done if r is not longest]
    rest = [rest[i] for i in np.random.default_rng([seed, 5]).permutation(len(rest))]
    pick = [longest]
    for r in rest:
        if sum(len(p.req.output) for p in pick) >= tokens or len(pick) >= most:
            break
        pick.append(r)
    return pick


def kv_of(cfg: dict) -> dict:
    """The cache precision the configuration states, for the reference."""
    return dict(cfg["packkv"], capacity=cfg["engine"]["capacity"])


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(run, cfg: dict, mix: dict, seed: int, ref, *,
          control: bool = False) -> dict:
    """The numbers compared, each with its limit: ``{name: {"value": v,
    "max": limit}}`` (or ``"min"`` for a floor); with ``control``, also
    the readings of both numbers by the reference at each precision one
    step below bfloat16 (int8, fp8), which are read and not judged."""
    c = cfg["check"]
    limit = c["logit_gap_limit"]
    done = finished(run)
    out = {"finished": {"value": len(done), "min": 1},
           "short_outputs": {"value": sum(
               1 for r in done if len(r.req.output) != r.max_new), "max": 0}}
    pick = sample(run, seed, c["sample_tokens"], c["sample_requests"])
    if not pick:
        out["logit_gap"] = {"value": None, "max": limit}
        out["prefill_logit_err"] = {"value": None,
                                    "max": c["prefill_logit_err_limit"]}
        return out
    pad = max(mix["prompt_len"]["values"]) + max(mix["output_len"]["values"])
    seqs = [(np.asarray(r.req.tokens), np.asarray(r.req.output)) for r in pick]
    gaps = ref.served_gaps(seed, cfg["model"], seqs, pad, kv=kv_of(cfg),
                           lowp=ref.LOWER if control else (),
                           bf16=cfg["dtype"] == "bfloat16")
    out["compared_tokens"] = {"value": int(sum(len(s[1]) for s in seqs)),
                              "min": 1}
    out["logit_gap"] = {"value": float(max(g["served"].max() for g in gaps)),
                        "max": limit}
    mine = [run.logits.get(r.rid) for r in pick]
    out["prefill_logit_err"] = {
        "value": None if any(x is None for x in mine) else max(
            rel_err(x, g["first"]) for x, g in zip(mine, gaps)),
        "max": c["prefill_logit_err_limit"]}
    for mode in ref.LOWER if control else ():
        out[f"{mode}_logit_gap"] = {"value": float(max(g[mode].max()
                                                       for g in gaps))}
        out[f"{mode}_prefill_logit_err"] = {"value": max(
            rel_err(g[mode + "_first"], g["first"]) for g in gaps)}
    return out


def passed(numbers: dict) -> bool:
    """Every judged number within its limit; a number or a limit that is
    missing fails."""
    for d in numbers.values():
        v = d["value"]
        if "max" in d and (v is None or d["max"] is None or v > d["max"]):
            return False
        if "min" in d and (v is None or v < d["min"]):
            return False
    return True
