"""Profiler capture of the measured window, and its reduction.

``capture`` wraps the window in ``jax.profiler`` tracing; ``load`` turns
the ``.xplane.pb`` it writes into three flat lists on one clock (ns):
device operations, device programs (XLA modules) and the harness's own
host spans (``bench.*`` annotations). Everything after that is plain
arithmetic on intervals, kept here so that every change computes it the
same way, and checked by the tests on a recorded trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import fnmatch
import glob
import os
import shutil
import tempfile

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    start: float  # ns
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    ops: list[Event]  # device operations of the first traced chip
    modules: list[Event]  # device programs, one event per execution
    spans: list[Event]  # host spans of the harness

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        """From ``{"ops", "modules", "spans"}`` lists of [start, end, name]
        (the recorded fixture's form)."""
        f = lambda rows: [Event(*r) for r in rows]
        return cls(f(d["ops"]), f(d["modules"]), f(d["spans"]))

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        """(start, end) of the harness span that marks the window."""
        for s in self.spans:
            if s.name == name:
                return s.start, s.end
        raise LookupError(f"no {name} span in the trace")


@contextlib.contextmanager
def capture():
    """Trace what runs inside; yields a dict that holds ``path`` (the
    ``.xplane.pb``) afterwards. The directory goes under ``TMPDIR`` and
    is removed by ``cleanup``."""
    import jax

    d = tempfile.mkdtemp(prefix="bench_trace_")
    out = {"dir": d, "path": None}
    jax.profiler.start_trace(d)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        out["path"] = found[0] if found else None


def cleanup(cap: dict) -> None:
    shutil.rmtree(cap["dir"], ignore_errors=True)


def _op_name(text: str) -> str:
    """An operation's event is its HLO text; keep the op's name and, for a
    custom call, its target (``%closed_call.51 (tpu_custom_call)``)."""
    name = text.split(" = ", 1)[0]
    i = text.find('custom_call_target="')
    if i >= 0:
        name += " (" + text[i + 20:text.find('"', i + 20)] + ")"
    return name


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: operations and programs of the first device
    plane, and every host event named ``bench.*``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    devices = sorted(p.name for p in pd.planes
                     if p.name.startswith("/device:") and
                     any(l.name == OPS_LINE for l in p.lines))
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Event(ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
        elif devices and plane.name == devices[0]:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append(Event(ev.start_ns,
                                         ev.start_ns + ev.duration_ns,
                                         _op_name(ev.name)))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        modules.append(Event(ev.start_ns,
                                             ev.start_ns + ev.duration_ns,
                                             ev.name))
    for evs in (ops, modules, spans):
        evs.sort(key=lambda e: (e.start, -e.end))
    return Trace(ops, modules, spans)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def clip(evs: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in evs
            if e.end > lo and e.start < hi]


def union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge intervals into disjoint ones, in order."""
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Time in [lo, hi) in which some operation ran on the device."""
    return sum(b - a for a, b in union(clip(tr.ops, lo, hi)))


def idle_gaps(tr: Trace, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi) in which no operation ran."""
    gaps, t = [], lo
    for a, b in union(clip(tr.ops, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def host_doing(tr: Trace, t: float) -> str:
    """The innermost harness span open at time ``t`` (``idle`` if none)."""
    best = None
    for s in tr.spans:
        if s.start <= t < s.end and s.name != "bench.window":
            if best is None or s.end - s.start < best.end - best.start:
                best = s
    return best.name[len(SPAN_PREFIX):] if best else "idle"


def match(name: str, patterns) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def waited_programs(tr: Trace, span: str) -> set[str]:
    """The programs the host waited for inside each ``span``: in each, the
    device program whose execution ended last within it. A dispatch that
    blocks on its result (a decode launch) is found so whatever its
    program is named."""
    out = set()
    for s in tr.spans:
        if s.name != span:
            continue
        inside = [m for m in tr.modules if s.start <= m.end <= s.end]
        if inside:
            out.add(max(inside, key=lambda m: m.end).name)
    return out


def module_ns(tr: Trace, names, lo: float, hi: float) -> float:
    """Device time of the executions of the programs named in ``names``."""
    return sum(b - a for a, b in
               clip([m for m in tr.modules if m.name in names], lo, hi))


def op_ns(tr: Trace, patterns, lo: float, hi: float, within=None) -> float:
    """Device time of the operations whose name matches, optionally only
    those inside an execution of the programs named in ``within``."""
    evs = [e for e in tr.ops if match(e.name, patterns)]
    if within is not None:
        runs = [(m.start, m.end) for m in tr.modules if m.name in within]
        evs = [e for e in evs if any(a <= e.start and e.end <= b
                                     for a, b in runs)]
    return sum(b - a for a, b in clip(evs, lo, hi))


def self_ns(tr: Trace, lo: float, hi: float) -> dict[str, float]:
    """Each operation's time less that of the operations nested in it
    (a loop's body, a call's callee), summed by name over [lo, hi)."""
    tot: dict[str, float] = {}
    stack: list[list] = []  # [end, name, own duration, children's]

    def close(top):
        tot[top[1]] = tot.get(top[1], 0.0) + top[2] - top[3]

    for e in tr.ops:
        if e.end <= lo or e.start >= hi:
            continue
        while stack and stack[-1][0] <= e.start:
            close(stack.pop())
        dur = min(e.end, hi) - max(e.start, lo)
        if stack:
            stack[-1][3] += dur
        stack.append([e.end, e.name, dur, 0.0])
    while stack:
        close(stack.pop())
    return tot


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device operations that took most time (by self time), and the
    longest idle gaps named by what the host was doing in them (s)."""
    ops = sorted(self_ns(tr, lo, hi).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[host_doing(tr, (a + b) / 2), (b - a) * 1e-9]
                          for a, b in gaps]}
