"""The serving program's own host spans in a profile, beside the harness's.

The engine marks its scheduler step, admissions and decode launches with
``packkv.*`` ``jax.profiler`` annotations (``repro.serving.engine.span``),
which land in the same ``.xplane.pb`` as the device operations, on the same
clock, with their arguments (step number, bucket, grid steps, ...).
``load`` reads them. ``install`` makes ``bench.trace.load`` keep them on
the ``Trace`` it returns (``program_spans``) and ``bench.trace.host_doing``
name an idle gap ``<harness span>/<innermost program span>`` (for example
``decode/decode.wait``); a trace without such spans (a program that has
none, the recorded ``trace_v5e.json`` fixture) reads exactly as before.
"""
from __future__ import annotations

import dataclasses

from bench import trace as tr

PREFIX = "packkv."


@dataclasses.dataclass
class Span:
    start: float  # ns, the device trace's clock
    end: float
    name: str
    args: dict


def from_json(rows) -> list[Span]:
    """From [start, end, name, args] rows (the recorded fixture's form)."""
    return [Span(*r) for r in rows]


def load(path: str) -> list[Span]:
    """Every host event named ``packkv.*`` in an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name, dict(ev.stats)))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def of(trace) -> list[Span]:
    """The program spans a loaded trace carries (none if it has none)."""
    return getattr(trace, "program_spans", [])


def innermost(spans: list[Span], t: float) -> Span | None:
    """The shortest program span open at time ``t``."""
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None or
                                     s.end - s.start < best.end - best.start):
            best = s
    return best


def step_host_ns(spans: list[Span], lo: float, hi: float) -> list[float]:
    """For each ``packkv.step`` span inside [lo, hi]: its duration less the
    time its ``packkv.*.wait`` spans cover, i.e. the host's own work in
    the step, which the device waits on."""
    waits = [s for s in spans if s.name.endswith(".wait")]
    out = []
    for s in spans:
        if s.name == PREFIX + "step" and lo <= s.start and s.end <= hi:
            blocked = tr.union(tr.clip(waits, s.start, s.end))
            out.append(s.end - s.start - sum(b - a for a, b in blocked))
    return out


def install() -> None:
    """Have ``bench.trace`` keep and name program spans (once)."""
    if getattr(tr.load, "keeps_program_spans", False):
        return
    base_load, base_doing = tr.load, tr.host_doing

    def load_with_spans(path: str):
        trace = base_load(path)
        trace.program_spans = load(path)
        return trace

    def host_doing(trace, t: float) -> str:
        doing = base_doing(trace, t)
        inner = innermost(of(trace), t)
        return doing if inner is None else \
            f"{doing}/{inner.name[len(PREFIX):]}"

    load_with_spans.keeps_program_spans = True
    tr.load, tr.host_doing = load_with_spans, host_doing
