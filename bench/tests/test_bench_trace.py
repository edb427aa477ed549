"""The trace reduction on a recorded trace (``fixtures/trace_v5e.json``:
59 ms of a minitron-4b decode launch on one TPU v5e), each number checked
against a brute-force count over a nanosecond grid."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import spec
from bench import trace as tr

FIXTURE = Path(__file__).parent / "fixtures" / "trace_v5e.json"


@pytest.fixture(scope="module")
def rec():
    return tr.Trace.from_json(json.loads(FIXTURE.read_text()))


def _mask(intervals, lo, hi):
    m = np.zeros(int(hi - lo), bool)
    for a, b in intervals:
        m[int(max(a, lo) - lo):int(min(b, hi) - lo)] = True
    return m


def test_busy_and_idle_share_match_a_brute_force_count(rec):
    lo, hi = rec.window()
    # the fixture's times are whole nanoseconds, so the grid is exact
    busy = _mask([(e.start, e.end) for e in rec.ops], lo, hi).sum()
    assert tr.busy_ns(rec, lo, hi) == pytest.approx(busy)
    gaps = tr.idle_gaps(rec, lo, hi)
    assert sum(b - a for a, b in gaps) == pytest.approx((hi - lo) - busy)
    assert 0.0 < 1 - busy / (hi - lo) < 0.05  # the launch keeps it busy


def test_kernel_time_by_name_inside_the_decode_program(rec):
    lo, hi = rec.window()
    decode = tr.waited_programs(rec, "bench.decode")
    assert decode == {"jit__unknown(12095887986775668963)"}
    kernels = spec.reader("attn_roofline").KERNELS
    got = tr.op_ns(rec, kernels, lo, hi, within=decode)
    want = sum(e.end - e.start for e in rec.ops
               if e.name == "%closed_call.51 (tpu_custom_call)")
    assert got == pytest.approx(want) and got > 0
    assert tr.op_ns(rec, kernels, lo, hi, within=set()) == 0
    assert tr.module_ns(rec, decode, lo, hi) == pytest.approx(
        sum(m.end - m.start for m in rec.modules if m.name in decode))


def test_self_time_never_counts_a_nested_op_twice(rec):
    lo, hi = rec.window()
    self_ns = tr.self_ns(rec, lo, hi)
    # nested ops' self times tile their union exactly once
    assert sum(self_ns.values()) == pytest.approx(tr.busy_ns(rec, lo, hi),
                                                  rel=1e-9)
    b = tr.breakdown(rec, lo, hi)
    assert b["device_ops"][0][0] == "%closed_call.51 (tpu_custom_call)"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] in {"decode", "step", "generator", "idle"}
