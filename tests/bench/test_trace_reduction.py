"""The trace reduction: on a handmade device trace with known intervals, and
on a trace the JAX profiler wrote on the CPU, read through ``load_xplane``."""
from __future__ import annotations

import dataclasses
import json
import types

import numpy as np
import pytest

from benchtest_util import DATA, REPO
from benchlib import cell, spec, trace

# Written by the JAX profiler on the CPU around four calls of a jitted
# ``tanh(x @ x.T).sum()`` on a 128 x 128 input, each call inside a
# ``bench.submit`` annotation and all four inside ``bench.traffic``.
CPU_XPLANE = DATA / "cpu_jit.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    rows = json.loads((DATA / "handmade_events.json").read_text())
    events = [trace.Event(*row) for row in rows]
    span = next(e for e in events if e.name == "bench.traffic")
    return trace.Trace(events, span.start_ns, span.end_ns)


def test_planes_and_lines(tr):
    assert tr.devices == ["/device:TPU:0"]
    assert len(tr.ops()) == 7
    assert [e.name for e in tr.modules()] == ["jit_fn(1379)", "jit_fn(1379)",
                                              "jit_other"]


def test_busy_and_idle_share_are_exact(tr):
    # union of op intervals clipped to [0, 1000) us: 20+200+80+200+50+20
    assert tr.busy_s() == pytest.approx(570e-6, abs=1e-15)
    assert tr.window_s == pytest.approx(1000e-6, abs=1e-15)
    assert tr.idle_share() == pytest.approx(0.43, abs=1e-12)


def test_gaps_and_union():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    assert trace.gaps([], 2, 4) == [(2, 4)]


def _params(metric):
    return json.loads((REPO / "bench" / "metrics" / f"{metric}.json")
                      .read_text())["params"]


def test_program_and_kernel_times(tr):
    """The committed patterns pick the program's executions and each
    kernel's ops by their names in a TPU trace, and not the fusion that
    consumes a kernel's output."""
    runs = tr.matching(tr.modules(), _params("program_device_ms.steady")
                       ["program"])
    assert len(runs) == 2
    assert sum(e.dur_ns for e in runs) == 500e3
    dw = tr.matching(tr.ops(), _params("dwconv_roofline.steady")["pattern"])
    qg = tr.matching(tr.ops(), _params("qgemm_roofline.offline")["pattern"])
    assert sum(e.dur_ns for e in dw) == 350e3
    assert sum(e.dur_ns for e in qg) == 80e3


def test_breakdown_labels_gaps_with_host_spans(tr):
    b = cell._breakdown(tr)
    assert b["idle_gaps"][0] == ["no host span", pytest.approx(200e-6)]
    assert ["bench.submit", pytest.approx(100e-6)] in b["idle_gaps"]
    assert b["device_ops"][0][0].startswith("%vmap_jit_dwconv3x3_bands__.9")
    assert b["device_ops"][0][1] == pytest.approx(200e-6)
    assert len(b["idle_gaps"]) == 5


def test_readers_on_the_trace(tr):
    bench = REPO / "bench"
    rec = types.SimpleNamespace(trace=tr, session={"requests": 10})
    idle = spec.load_module(bench / "readers" / "device_idle.py")
    prog = spec.load_module(bench / "readers" / "program_device_ms.py")
    assert idle.read(rec, {}) == pytest.approx(43.0)
    assert prog.read(rec, _params("program_device_ms.offline")) == \
        pytest.approx(0.25)
    assert prog.read(rec, {"program": "nothing_matches"}) is None


@pytest.fixture(scope="module")
def cpu_events():
    return trace.load_xplane(CPU_XPLANE)


def test_xplane_spans_and_calls(cpu_events):
    """``load_xplane`` reads the host annotations and the executions of the
    jitted function, in nanoseconds on one clock."""
    traffic = [e for e in cpu_events if e.name == "bench.traffic"]
    submits = [e for e in cpu_events if e.name == "bench.submit"]
    assert len(traffic) == 1 and len(submits) == 4
    t = traffic[0]
    assert all(t.start_ns <= s.start_ns and s.end_ns <= t.end_ns
               for s in submits)
    assert all(e.plane.startswith("/host:") for e in cpu_events)
    dots = [e for e in cpu_events if e.name.startswith("dot_general")]
    assert len(dots) == 4
    # each execution's matmul runs inside the call that submitted it
    for d in dots:
        assert sum(s.start_ns <= d.start_ns < s.end_ns for s in submits) == 1


def _executor_line(events):
    lines = {e.line for e in events if e.name.startswith("dot_general")}
    assert len(lines) == 1
    return lines.pop()


def test_xplane_reduction_matches_a_count_of_nanoseconds(cpu_events):
    """The XLA executor thread's events, put on a device's op line, reduce
    to the busy time and idle share that counting each covered nanosecond
    of the window gives."""
    line = _executor_line(cpu_events)
    ops = [dataclasses.replace(e, plane="/device:CPU:0", line=trace.OPS_LINE)
           for e in cpu_events if e.line == line]
    span = next(e for e in cpu_events if e.name == "bench.traffic")
    lo, hi = span.start_ns, span.end_ns
    tr = trace.Trace(cpu_events + ops, lo, hi)
    assert tr.devices == ["/device:CPU:0"]

    covered = np.zeros(int(hi - lo), bool)
    for e in ops:
        covered[int(max(e.start_ns, lo) - lo):int(min(e.end_ns, hi) - lo)] = True
    busy_ns = int(covered.sum())
    assert 0 < busy_ns < hi - lo
    assert tr.busy_s() == pytest.approx(busy_ns * 1e-9, rel=1e-12)
    assert tr.idle_share() == pytest.approx(1 - busy_ns / (hi - lo),
                                            rel=1e-12)
    gaps = trace.gaps(tr.busy("/device:CPU:0"), lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(
        (hi - lo) - busy_ns, rel=1e-12)
    b = cell._breakdown(tr)
    assert b["idle_gaps"][0][1] == pytest.approx(
        max(e - s for s, e in gaps) * 1e-9, rel=1e-12)
