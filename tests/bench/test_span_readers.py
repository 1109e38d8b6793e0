"""The readers of the program's spans and ticket stamps: on a handmade trace
whose answers are known (``data/handmade_spans.json``, in nanoseconds; the
window is its ``bench.traffic`` span, [0, 1 ms)), against a count of each
covered nanosecond, and on fabricated tickets."""
from __future__ import annotations

import dataclasses
import json
import math
import types

import numpy as np
import pytest

from benchtest_util import DATA, REPO
from benchlib import spec, trace

BENCH = REPO / "bench"
IDLE_SHARES = ("idle_in_wait.steady", "idle_in_host.steady",
               "idle_no_work.steady")


def _metric(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def _reader(name):
    return spec.load_module(BENCH / "readers" / f"{name}.py")


def _read(metric, rec):
    m = _metric(metric)
    return _reader(m["reader"]).read(rec, m.get("params", {}))


@pytest.fixture(scope="module")
def events():
    return [trace.Event(*row)
            for row in json.loads((DATA / "handmade_spans.json").read_text())]


def _rec(events, lo=0.0, hi=1e6):
    return types.SimpleNamespace(trace=trace.Trace(events, lo, hi))


def _with_second_device(events):
    """A second device that is busy in [0, 0.5 ms) only."""
    return events + [trace.Event("/device:TPU:1", trace.OPS_LINE, "fusion.9",
                                 0.0, 5e5)]


def _brute_idle_during(events, spans, lo, hi):
    """Idle share (%) while any of ``spans`` is open, by marking every
    nanosecond of the window, averaged over the devices."""
    devices = sorted({e.plane for e in events if e.plane.startswith("/dev")})
    held = np.zeros(int(hi - lo), bool)
    for e in events:
        if e.name in spans and e.plane.startswith("/host:"):
            held[int(max(e.start_ns, lo) - lo):
                 int(max(min(e.end_ns, hi) - lo, 0))] = True
    shares = []
    for d in devices:
        busy = np.zeros(int(hi - lo), bool)
        for e in events:
            if e.plane == d and e.line == trace.OPS_LINE:
                busy[int(max(e.start_ns, lo) - lo):
                     int(max(min(e.end_ns, hi) - lo, 0))] = True
        shares.append(100.0 * np.sum(~busy & held) / (hi - lo))
    return sum(shares) / len(shares)


@pytest.mark.parametrize("metric,expected", [
    ("idle_in_wait.steady", 17.0),    # 40 + 10 + 120 us of 1000
    ("idle_in_host.steady", 6.0),     # 5 + 15 + 40
    ("idle_no_work.steady", 7.0),     # 60 + 10 (the last span clipped)
])
def test_idle_during_known_answers(events, metric, expected):
    assert _read(metric, _rec(events)) == pytest.approx(expected, abs=1e-9)


@pytest.mark.parametrize("two_devices", [False, True])
@pytest.mark.parametrize("window", [(0.0, 1e6), (1e5, 9e5), (2.5e5, 9.9e5)])
@pytest.mark.parametrize("metric", IDLE_SHARES)
def test_idle_during_matches_a_count_of_nanoseconds(events, metric, window,
                                                     two_devices):
    evs = _with_second_device(events) if two_devices else events
    spans = set(_metric(metric)["params"]["spans"])
    assert _read(metric, _rec(evs, *window)) == pytest.approx(
        _brute_idle_during(evs, spans, *window), abs=1e-9)


def test_idle_during_clips_to_the_window(events):
    # [100, 900) us: ready/fetch hold 40 + 10 + 100 us of idle in 800 us;
    # the ready spans' idle past 900 us falls outside
    assert _read("idle_in_wait.steady", _rec(events, 1e5, 9e5)) == \
        pytest.approx(100.0 * 150 / 800, abs=1e-9)
    # the idle span before the window, where the device ran nothing, and
    # the part of the last one past its end count for nothing
    assert _read("idle_no_work.steady", _rec(events)) == pytest.approx(7.0)


def test_overlapping_spans_count_once(events):
    """Two session.ready spans overlap in [850, 900) us: their union holds
    120 us of idle, not 120 + 70."""
    ready = _reader("idle_during").read(_rec(events),
                                        {"spans": ["session.ready"]})
    assert ready == pytest.approx(16.0, abs=1e-9)    # 40 + 120


@pytest.mark.parametrize("two_devices", [False, True])
def test_shares_and_the_rest_add_up_to_device_idle(events, two_devices):
    evs = _with_second_device(events) if two_devices else events
    rec = _rec(evs)
    shares = [_read(m, rec) for m in IDLE_SHARES]
    every = set().union(*(_metric(m)["params"]["spans"] for m in IDLE_SHARES))
    idle = _read("device_idle.steady", rec)
    rest = idle - _brute_idle_during(evs, every, 0.0, 1e6)
    assert sum(shares) + rest == pytest.approx(idle, abs=1e-9)
    if not two_devices:
        assert idle == pytest.approx(33.0) and rest == pytest.approx(3.0)


def test_no_spans_no_reading(events):
    """A program without the spans (or a run without a trace) gives
    nothing, so the metric is left out of the result line."""
    bare = [e for e in events if not e.name.startswith(("serve.",
                                                        "session."))]
    for metric in IDLE_SHARES + ("host_dispatch_ms.steady",):
        assert _read(metric, _rec(bare)) is None
        assert _read(metric, types.SimpleNamespace(trace=None)) is None


def test_span_mean_counts_spans_that_start_in_the_window(events):
    # session.dispatch spans of 25 and 15 us start in the window; the one
    # of 30 us starts before it
    assert _read("host_dispatch_ms.steady", _rec(events)) == \
        pytest.approx(0.020, abs=1e-12)
    assert _read("host_dispatch_ms.offline", _rec(events)) == \
        pytest.approx(0.020, abs=1e-12)
    shifted = [dataclasses.replace(e, start_ns=e.start_ns + 2e6)
               if e.name == "session.dispatch" else e for e in events]
    assert _read("host_dispatch_ms.steady", _rec(shifted)) is None


def _ticket(queued, dispatched, completed):
    return types.SimpleNamespace(queued_at=queued, dispatched_at=dispatched,
                                 completed_at=completed)


def _requests():
    """Five answered requests, queue waits 5, 1, 3, 2, 4 ms and service
    20, 30, 10, 50, 40 ms, and three unanswered ones that would dominate
    both quantiles if they were counted."""
    ok = [types.SimpleNamespace(status="ok", ticket=_ticket(
        t, t + w * 1e-3, t + w * 1e-3 + s * 1e-3))
        for t, w, s in zip(range(5), (5, 1, 3, 2, 4), (20, 30, 10, 50, 40))]
    late = _ticket(0.0, 9.0, 99.0)
    return ok + [types.SimpleNamespace(status="error", ticket=late),
                 types.SimpleNamespace(status="missing", ticket=late),
                 types.SimpleNamespace(status="refused", ticket=None)]


def test_ticket_interval_nearest_rank_p50():
    rec = types.SimpleNamespace(window_requests=_requests())
    assert _read("queue_wait_p50_ms.steady", rec) == pytest.approx(3.0)
    assert _read("service_p50_ms.steady", rec) == pytest.approx(30.0)
    reader = _reader("ticket_interval")
    params = dict(_metric("queue_wait_p50_ms.steady")["params"], q=1.0)
    assert reader.read(rec, params) == pytest.approx(5.0)


def test_ticket_interval_skips_what_lacks_stamps():
    # a ticket that was never stamped (NaN), and one from a program whose
    # tickets have no such stamps at all
    unstamped = types.SimpleNamespace(status="ok", ticket=_ticket(
        1.0, math.nan, 2.0))
    bare = types.SimpleNamespace(status="ok", ticket=types.SimpleNamespace(
        completed_at=2.0))
    rec = types.SimpleNamespace(window_requests=[unstamped, bare])
    assert _read("queue_wait_p50_ms.steady", rec) is None
    rec = types.SimpleNamespace(window_requests=[unstamped, bare]
                                + _requests())
    assert _read("queue_wait_p50_ms.steady", rec) == pytest.approx(3.0)


NEW = {"mnv2-spatial-steady": {"queue_wait_p50_ms.steady",
                               "service_p50_ms.steady",
                               "host_dispatch_ms.steady",
                               "idle_in_wait.steady", "idle_in_host.steady",
                               "idle_no_work.steady"},
       "mnv2-neuron-offline": {"host_dispatch_ms.offline"}}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_new_metrics_load_for_their_cell(cell):
    bm = spec.Benchmark(REPO)
    c = bm.cell(cell)
    got = {m.name: m for m in c.per_layer}
    assert NEW[cell] <= set(got)
    for other in set().union(*NEW.values()) - NEW[cell]:
        assert other not in got
    entries = {m["name"]: m for m in bm.doc["per_layer"]}
    for name in NEW[cell]:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["workloads"] == [cell]
        assert (bm.bench / "readers" / f"{got[name].reader}.py").exists()
