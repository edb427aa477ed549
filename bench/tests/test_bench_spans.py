"""The program's own spans, names and counters as the benchmark reads them.

A short traced drive of a smoke-size cell on the CPU checks that the
harness's trace keeps the engine's ``packkv.*`` spans, nested and with
their arguments, and that the counters they carry add up to the
scheduler's. A recorded chip trace (``fixtures/trace_v5e_spans.json``: a
slice of a minitron-4b window around one admission on one TPU v5e) checks
the four readers against brute-force counts, segment by segment, and
that the programs and kernel the older readers find by where the host
waited are the ones their names say.
"""
import json
import time
import types
from pathlib import Path

import numpy as np
import pytest

from bench import launch_fit
from bench import program_spans as ps
from bench import serve, smoke, spec
from bench import trace as tr

FIXTURE = Path(__file__).parent / "fixtures" / "trace_v5e_spans.json"
SEED = 2**31 + 91
STEP_ARGS = {"step", "occupied", "queued"}


def _inside(inner, outer) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end


def _within(span, spans, name) -> bool:
    return any(_inside(span, s) for s in spans if s.name == name)


# -- a traced drive on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def traced():
    spec.reader("step_host_ms")  # has bench.trace keep program spans
    cell = smoke.shrink(spec.Cell("minitron-4b.long_decode", 1,
                                  spec.config("minitron-4b"),
                                  spec.traffic("long_decode"), []))
    ref = spec.reference(cell.config["reference"])
    run, _ = serve.drive(cell.config, cell.traffic, SEED, 3.0, ref,
                         t_start=time.perf_counter(), trace=True,
                         log=lambda msg: None)
    return run


def test_steps_nest_the_decode_spans_with_their_arguments(traced):
    spans = ps.of(traced.trace)
    lo, hi = traced.window_ns
    steps = [s for s in spans if s.name == "packkv.step"
             and lo <= s.start and s.end <= hi]
    assert steps and all(set(s.args) == STEP_ARGS for s in steps)
    for name in ("reap", "admit", "decode.plan", "decode.dispatch",
                 "decode.wait", "decode.emit"):
        mine = [s for s in spans if s.name == "packkv." + name
                and lo <= s.start and s.end <= hi]
        assert mine, name
        assert all(_within(s, spans, "packkv.step") for s in mine), name
    waits = [s for s in spans if s.name == "packkv.decode.wait"
             and lo <= s.start and s.end <= hi]
    # the engine's waits sit inside the harness's decode dispatch spans
    assert all(_within(s, traced.trace.spans, "bench.decode") for s in waits)
    assert all("n_exec" in s.args for s in waits)
    assert all({"bucket", "n_steps"} <= set(s.args) for s in spans
               if s.name == "packkv.decode.dispatch")
    t = (waits[0].start + waits[0].end) / 2
    assert tr.host_doing(traced.trace, t) == "decode/decode.wait"


def test_span_arguments_add_up_to_the_counters(traced):
    spans = ps.of(traced.trace)
    lo, hi = traced.window_ns
    emits = [s for s in spans if s.name == "packkv.decode.emit"
             and lo <= s.start <= hi]
    grid = sum(s.args["grid_steps"] for s in emits)
    assert grid == traced.delta("attn_grid_steps") > 0
    assert sum(s.args["dead_steps"] for s in emits) == \
        traced.delta("attn_dead_steps")
    prefills = [s for s in spans if s.name == "packkv.prefill"
                and lo <= s.start <= hi]
    assert all(_within(s, spans, "packkv.admit") for s in prefills)
    assert all(s.args["kind"] in ("insert", "chunk", "final") and
               {"rid", "tokens", "n_ctx"} <= set(s.args) for s in prefills)
    assert sum(s.args["tokens"] for s in prefills) == \
        traced.delta("prefill_tokens")


def test_readers_of_the_counters_and_spans_read_the_cpu_drive(traced):
    share = spec.reader("attn_dead_step_share").read(traced)
    assert 0.0 < share < 1.0
    host = spec.reader("step_host_ms").read(traced)
    lo, hi = traced.window_ns
    steps = [s.end - s.start for s in ps.of(traced.trace)
             if s.name == "packkv.step" and lo <= s.start and s.end <= hi]
    assert 0.0 < host < np.mean(steps) * 1e-6


def test_a_program_without_spans_or_counters_reads_nothing():
    # the parent program: no counters in its stats, no spans in its trace
    bare = tr.Trace([], [], [tr.Event(0.0, 10.0, "bench.window")])
    run = types.SimpleNamespace(trace=bare, window_ns=(0.0, 10.0),
                                stats0={}, stats1={})
    for name in ("attn_dead_step_share", "attn_us_per_grid_step",
                 "prefill_ms_per_ktok", "step_host_ms"):
        assert spec.reader(name).read(run) is None, name


def test_new_readers_declare_what_the_benchmark_says():
    entries = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in ("attn_dead_step_share", "attn_us_per_grid_step",
                 "prefill_ms_per_ktok", "step_host_ms"):
        mod, m = spec.reader(name), entries[name]
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == \
            (m["unit"], m["layer"], m["moves"]) and "workloads" not in m


# -- a recorded chip trace -----------------------------------------------------

KERNEL = "%packkv_paged_attention"
PREFILL = ("jit_packkv_insert(", "jit_packkv_chunk_step(",
           "jit_packkv_chunk_final(")


@pytest.fixture(scope="module")
def rec():
    spec.reader("step_host_ms")  # has bench.trace keep program spans
    d = json.loads(FIXTURE.read_text())
    trace = tr.Trace.from_json(d)
    trace.program_spans = ps.from_json(d["program_spans"])
    return trace


def _covered_ns(want, lo, hi, without=()):
    """Time in [lo, hi) that some interval of ``want`` covers and none of
    ``without`` does, segment by segment between all their end points (a
    nanosecond grid over this slice would take gigabytes)."""
    pts = sorted({lo, hi, *(x for iv in (*want, *without) for x in iv
                            if lo < x < hi)})
    a, b = np.array(pts[:-1]), np.array(pts[1:])
    mid = (a + b) / 2

    def cover(ivs):
        if not ivs:
            return np.zeros(len(mid), bool)
        s, e = np.array(ivs, float).T
        return ((s <= mid[:, None]) & (mid[:, None] < e)).any(axis=1)

    return float((b - a)[cover(list(want)) & ~cover(list(without))].sum())


def _run(trace, **stats):
    return types.SimpleNamespace(
        trace=trace, window_ns=trace.window(), stats0=dict.fromkeys(stats, 0),
        stats1=stats, delta=lambda name: stats[name])


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_the_recording_holds_launches_and_an_admission(rec):
    spans = ps.of(rec)
    assert _named(spans, "packkv.prefill") and \
        _named(spans, "packkv.decode.dispatch")
    assert any(m.name.startswith("jit_packkv_decode_multi(")
               for m in rec.modules)
    assert any(m.name.startswith(PREFILL) for m in rec.modules)


def test_kernel_readers_match_a_brute_force_count(rec):
    lo, hi = rec.window()
    emits = _named(ps.of(rec), "packkv.decode.emit")
    grid = sum(s.args["grid_steps"] for s in emits)
    dead = sum(s.args["dead_steps"] for s in emits)
    run = _run(rec, attn_grid_steps=grid, attn_dead_steps=dead)
    kernel_ns = _covered_ns([(e.start, e.end) for e in rec.ops
                             if e.name.startswith(KERNEL) and
                             e.name.endswith(" (tpu_custom_call)")], lo, hi)
    assert kernel_ns > 0 and 0 < dead < grid
    assert spec.reader("attn_us_per_grid_step").read(run) == \
        pytest.approx(kernel_ns * 1e-3 / grid)
    assert spec.reader("attn_dead_step_share").read(run) == \
        pytest.approx(dead / grid)


def test_prefill_reader_matches_a_brute_force_count(rec):
    lo, hi = rec.window()
    tokens = sum(s.args["tokens"]
                 for s in _named(ps.of(rec), "packkv.prefill"))
    ns = _covered_ns([(m.start, m.end) for m in rec.modules
                      if m.name.startswith(PREFILL)], lo, hi)
    assert ns > 0 and tokens > 0
    got = spec.reader("prefill_ms_per_ktok").read(_run(rec,
                                                       prefill_tokens=tokens))
    assert got == pytest.approx(ns * 1e-6 / (tokens / 1000))


def test_step_host_reader_matches_a_brute_force_count(rec):
    lo, hi = rec.window()
    spans = ps.of(rec)
    waits = [(s.start, s.end) for s in spans if s.name.endswith(".wait")]
    host = []
    for s in _named(spans, "packkv.step"):
        if lo <= s.start and s.end <= hi:
            host.append(_covered_ns([(s.start, s.end)], lo, hi, waits))
    assert host
    got = spec.reader("step_host_ms").read(_run(rec))
    assert got == pytest.approx(np.mean(host) * 1e-6)


def test_the_heuristic_decode_program_and_kernel_are_the_named_ones(rec):
    lo, hi = rec.window()
    waited = tr.waited_programs(rec, "bench.decode")
    named = {m.name for m in rec.modules
             if m.name.startswith("jit_packkv_decode_multi(")}
    # where a tiny program ends after the launch inside its span (a dtype
    # conversion here), the heuristic takes it too; it adds no time to
    # speak of to the decode programs'
    assert named and named <= waited
    assert tr.module_ns(rec, waited - named, lo, hi) < \
        1e-3 * tr.module_ns(rec, named, lo, hi)
    kernels = spec.reader("attn_roofline").KERNELS
    inside = [(m.start, m.end) for m in rec.modules if m.name in waited]
    custom = {(e.start, e.end, e.name) for e in rec.ops
              if tr.match(e.name, kernels) and
              any(a <= e.start and e.end <= b for a, b in inside)}
    by_name = {(e.start, e.end, e.name) for e in rec.ops
               if tr.match(e.name, spec.reader(
                   "attn_us_per_grid_step").KERNELS)}
    assert custom == by_name and custom
    assert tr.op_ns(rec, kernels, lo, hi, within=waited) == \
        tr.op_ns(rec, spec.reader("attn_us_per_grid_step").KERNELS, lo, hi)


def test_idle_gaps_inside_a_step_carry_a_program_span_label(rec):
    lo, hi = rec.window()
    steps = _named(ps.of(rec), "packkv.step")
    labels = tr.breakdown(rec, lo, hi, top=1000)["idle_gaps"]
    gaps = sorted(tr.idle_gaps(rec, lo, hi), key=lambda g: g[0] - g[1])
    assert len(labels) == len(gaps)
    for (label, _), (a, b) in zip(labels, gaps):
        t = (a + b) / 2
        if any(s.start <= t < s.end for s in steps):
            assert "/" in label, label


def test_launch_rows_price_each_launch_in_the_recording(rec):
    lo, hi = rec.window()
    kernels = spec.reader("attn_us_per_grid_step").KERNELS
    rows = launch_fit.launch_rows(rec, lo, hi, kernels)
    emits = _named(ps.of(rec), "packkv.decode.emit")
    assert len(rows) == len(emits) > 0
    for row, e in zip(rows, emits):
        assert row["live"] + row["dead"] == e.args["grid_steps"]
        assert row["dead"] == e.args["dead_steps"]
        # the slice holds only the end of this launch, past its kernels
        assert 0 <= row["kernel_ms"] <= row["program_ms"]


def test_fit_recovers_known_step_costs():
    rows = [{"live": live, "dead": dead,
             "kernel_ms": (13.0 * live + 0.5 * dead) * 1e-3}
            for live, dead in [(100, 50), (80, 90), (120, 10), (60, 60)]]
    got = launch_fit.fit(rows)
    assert (got["a_us"], got["b_us"]) == (pytest.approx(13.0),
                                          pytest.approx(0.5))
    assert got["r2"] == pytest.approx(1.0) and got["launches"] == 4
    assert got["max_rel_residual"] == pytest.approx(0.0, abs=1e-9)


def test_idle_time_inside_program_spans_on_the_recording(rec):
    lo, hi = rec.window()
    got = launch_fit.idle_by_span(rec, lo, hi)
    spans = ps.of(rec)
    busy = [(e.start, e.end) for e in rec.ops]
    inside = _covered_ns([(s.start, s.end) for s in spans], lo, hi, busy)
    assert got["idle_s"] == pytest.approx(_covered_ns([(lo, hi)], lo, hi,
                                                      busy) * 1e-9)
    assert got["in_program_span_s"] == pytest.approx(inside * 1e-9)
    step = _covered_ns([(s.start, s.end) for s in _named(spans,
                                                         "packkv.step")],
                       lo, hi, busy)
    assert got["by_name_s"]["step"] == pytest.approx(step * 1e-9)
