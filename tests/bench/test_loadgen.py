"""The benchmark's load generator through a real ``Server`` on the CPU: it
times requests from the instant they were due, a stalled server shows in
the tail, and refused requests count as failed."""
from __future__ import annotations

import contextlib
import json
import math
import time
import types

import numpy as np
import pytest

from benchtest_util import DATA, REPO
from benchlib import loadgen, reference, spec

from repro.api import Plan
from repro.core.quantize import quantize_model
from repro.serve import SLO, Overloaded, Server

BENCH = REPO / "bench"
TENANT = "t"


@pytest.fixture(scope="module")
def session():
    cfg = json.loads((DATA / "mnv2_smoke_int8_spatial.json").read_text())
    arch = spec.load_module(BENCH / "models" / f"{cfg['arch']}.py")
    params = arch.make_params(cfg, 5)
    layers = arch.layers(cfg)
    model = spec.program_model(BENCH, cfg, 5, params=params)
    calib = np.random.default_rng(0).standard_normal(
        (2, *cfg["input_shape"]), dtype=np.float32)
    qmodel = quantize_model(model, reference.calibrate(layers, params, calib))
    plan = Plan.from_json(DATA / "mnv2_smoke_int8_spatial.plan.json", model)
    sess = plan.compile(precision="int8", qmodel=qmodel, max_batch=4,
                        buckets=(1, 4))
    sess.warmup()
    return sess


def _drive(session, traffic, seconds=1.0, stall_s=0.0, slo=None):
    """One open-loop run; ``stall_s`` stalls the first dispatch after
    ``0.3 s`` of the window, as a server that stops would."""
    server = Server()
    server.add_tenant(TENANT, session, slo=slo or SLO(None, None),
                      warmup=False)
    real = session.dispatch_async
    t_stall = [None]

    def stalling(xs):
        if stall_s and t_stall[0] is None and \
                time.perf_counter() > t_start + 0.3:
            t_stall[0] = time.perf_counter()
            time.sleep(stall_s)
        return real(xs)

    session.dispatch_async = stalling
    pool = np.random.default_rng(1).standard_normal(
        (4, *session.model.input_shape), dtype=np.float32)
    try:
        with server:
            t_start = time.perf_counter()
            reqs, w0, w1 = loadgen.DRIVERS[traffic["arrivals"]](
                server, TENANT, traffic, pool, np.random.default_rng(2),
                seconds, Overloaded, lambda name: contextlib.nullcontext())
            loadgen.collect(reqs, w1 + 30)
    finally:
        del session.dispatch_async
    window = [r for r in reqs if w0 <= r.due < w1]
    return types.SimpleNamespace(requests=reqs, window_requests=window,
                                 window=(w0, w1), deadline=w1 + 30,
                                 seconds=seconds)


def _reader(name):
    return spec.load_module(BENCH / "readers" / f"{name}.py")


POISSON = {"arrivals": "poisson", "rate_rps": 60, "lead_s": 0.1}


def test_gaps_same_multiset_for_every_seed():
    a = loadgen.gaps(500, 100.0, np.random.default_rng(1))
    b = loadgen.gaps(500, 100.0, np.random.default_rng(2))
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert a.sum() == pytest.approx(5.0)


def test_latency_is_timed_from_the_due_instant(session):
    rec = _drive(session, POISSON)
    assert all(r.status == "ok" for r in rec.window_requests)
    assert len(rec.window_requests) == 60
    lat = sorted(r.done - r.due for r in rec.window_requests)
    p95 = _reader("latency_quantile").read(rec, {"q": 0.95})
    assert p95 == pytest.approx(1e3 * lat[math.ceil(0.95 * 60) - 1])
    # due <= submitted <= done for every request, and the generator's own
    # lateness is the submit stamp minus the due stamp
    assert all(r.due <= r.submitted <= r.done for r in rec.window_requests)
    late = _reader("gen_lateness").read(rec, {"q": 1.0})
    assert late == pytest.approx(1e3 * max(r.submitted - r.due
                                           for r in rec.window_requests))


def test_stalled_server_shows_in_the_tail(session):
    """A 1 s stall of the server lands in the tail however busy the CPU is:
    latency is timed from the due instant, so the requests due in the
    stall's first 0.2 s (a fifth of the window) read 0.8 s or more."""
    calm = _drive(session, POISSON)
    stalled = _drive(session, POISSON, stall_s=1.0)
    p95_calm = _reader("latency_quantile").read(calm, {"q": 0.95})
    p95_stalled = _reader("latency_quantile").read(stalled, {"q": 0.95})
    assert p95_stalled >= 800
    assert p95_stalled > p95_calm


def test_refused_requests_count_as_failed(session):
    rec = _drive(session, {"arrivals": "poisson", "rate_rps": 60,
                           "lead_s": 0.0},
                 stall_s=0.5, slo=SLO(p99_target_s=None, queue_cap=1))
    refused = [r for r in rec.window_requests if r.status == "refused"]
    assert len(refused) >= 0.05 * len(rec.window_requests)
    # a refused request is missing: it takes the longest latency the run
    # could have measured, so the tail sees it
    p100 = _reader("latency_quantile").read(rec, {"q": 1.0})
    assert p100 >= 1e3 * (rec.deadline - rec.window[1])


def test_closed_loop_keeps_its_requests_in_flight(session):
    rec = _drive(session, {"arrivals": "closed", "outstanding": 8,
                           "lead_s": 0.1})
    assert rec.window_requests and all(r.status == "ok"
                                       for r in rec.requests)
    rate = _reader("throughput").read(rec, {})
    w0, w1 = rec.window
    assert rate == sum(w0 <= r.done <= w1 for r in rec.requests) / 1.0
