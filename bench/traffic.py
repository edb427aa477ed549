"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) states a loop and length sets:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the previous one finished) or ``"open"`` (arrivals on a
  schedule at ``rate_per_s``, whether or not earlier requests finished).
* ``prompt_len`` / ``output_len``: ``{"values": [...], "p": [...]}``.
* ``deck``: how many (prompt, output) cards make one round of traffic.
  The cards hold the joint distribution in exact proportions.
* open loop only: ``burst`` (``none``, or ``{"every_s", "size"}`` for
  extra arrivals at once) and ``warmup_s`` (arrivals before the window
  opens, so the window starts in a steady state).

The seed draws the token ids (and, in the harness, the weights). The
sizes and the arrival times are the same for every seed, in one fixed
shuffled order: a seed that changed which sizes reach the window would
change how much work the window holds, and with it the timings.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Card:
    prompt_len: int
    output_len: int


@dataclasses.dataclass
class Arrival:
    """One request of an open loop, due ``due_s`` after the window opens
    (negative: during warm-up)."""
    due_s: float
    card: Card


ORDER = 20251217  # the one shuffle of sizes and gaps every seed shares


def deck(mix: dict, rnd: int = 0) -> list[Card]:
    """Round ``rnd`` of cards: the joint length distribution in integer
    proportions (largest remainders), in that round's fixed order."""
    pv, pp = mix["prompt_len"]["values"], mix["prompt_len"]["p"]
    ov, op = mix["output_len"]["values"], mix["output_len"]["p"]
    n = int(mix["deck"])
    for name, p in (("prompt_len", pp), ("output_len", op)):
        if abs(sum(p) - 1.0) > 1e-9 or min(p) < 0:
            raise ValueError(f"{name}.p must be a distribution, got {p}")
    want = np.outer(pp, op).ravel() * n
    cnt = np.floor(want).astype(int)
    order = np.argsort(-(want - cnt), kind="stable")
    cnt[order[: n - cnt.sum()]] += 1
    cards = [Card(int(pv[i // len(ov)]), int(ov[i % len(ov)]))
             for i, c in enumerate(cnt) for _ in range(c)]
    rng = np.random.default_rng([ORDER, 1, rnd])
    return [cards[i] for i in rng.permutation(len(cards))]


def cards(mix: dict):
    """Endless card stream: round after round of ``deck``."""
    r = 0
    while True:
        yield from deck(mix, r)
        r += 1


def arrivals(mix: dict, seconds: float) -> list[Arrival]:
    """Open-loop schedule over ``[-warmup_s, seconds)``.

    The span holds ``round(span * rate_per_s)`` arrivals. Their gaps are
    the exponential distribution's quantiles (a Poisson process with its
    mean exactly held), scaled to fill the span, in the fixed order; bursts
    add ``size`` arrivals at once every ``every_s``.
    """
    rate = float(mix["rate_per_s"])
    t0 = -float(mix.get("warmup_s", 0.0))
    span = seconds - t0
    n = max(1, round(span * rate))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = gaps[np.random.default_rng([ORDER, 2]).permutation(n)]
    gaps *= span / gaps.sum()
    times = list(t0 + np.cumsum(gaps) - gaps)
    burst = mix.get("burst", "none")
    if burst != "none":
        k = 1
        while (t := t0 + k * burst["every_s"]) < seconds:
            times += [t] * int(burst["size"])
            k += 1
    times.sort()
    stream = cards(mix)
    return [Arrival(float(t), next(stream)) for t in times]


def prompt_tokens(seed: int, rid: int, n: int, vocab: int) -> np.ndarray:
    """Request ``rid``'s prompt: ``n`` seeded token ids over ``[0, vocab)``
    (a function of the request's number, not of when it was sent)."""
    return np.random.default_rng([seed, 3, rid]).integers(
        0, vocab, n, dtype=np.int32)


def lengths(mix: dict) -> tuple[list[int], list[int]]:
    """The distinct prompt and output lengths a mix can send."""
    return (sorted(mix["prompt_len"]["values"]),
            sorted(mix["output_len"]["values"]))
