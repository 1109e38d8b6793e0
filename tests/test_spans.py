"""The program's profiler spans and request stamps: served through ``Server``
under ``jax.profiler.trace``, every dispatch leaves its six spans in order,
each carrying the dispatch's ``batch`` id that its tickets carry, and every
ticket's stamps are ordered on one clock.  With no profiler running the
spans are inert: outputs stay bit-identical."""
import pathlib
import time
import types
import warnings

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from conftest import small_cnn
from repro.api import Session
from repro.core import split_model
from repro.serve import Server

# threaded server: keep the module on one xdist worker, like test_serve
pytestmark = pytest.mark.xdist_group("runtime")

SPANS = ("serve.idle", "serve.form", "session.dispatch", "session.ready",
         "session.fetch", "serve.fulfill")
PER_DISPATCH = SPANS[1:]          # in the order one dispatch opens them


@pytest.fixture(scope="module")
def split():
    return split_model(small_cnn(), np.asarray([2.0, 1.0]))


@pytest.fixture(scope="module")
def xs():
    rng = np.random.default_rng(5)
    return rng.standard_normal((10, 3, 12, 12)).astype(np.float32)


@pytest.fixture(scope="module")
def ref(split, xs):
    sess = Session(split, precision="int8", seed=0, max_batch=4)
    return np.stack([sess.run(x) for x in xs])


def _server(split):
    srv = Server()
    srv.add_tenant("t0", split, precision="int8", seed=0, max_batch=4,
                   buckets=(1, 2, 4))
    return srv


def _prefill(srv, xs):
    """Queue requests before the scheduler runs, so they ride in
    multi-request batches (the admitted-but-unscheduled state)."""
    srv._running = True
    tickets = [srv.submit("t0", x) for x in xs]
    srv._running = False
    return tickets


def _spans(log_dir) -> list[types.SimpleNamespace]:
    """The program's spans in the trace written under ``log_dir``, with
    their ``batch`` metadata (None where a span carries none)."""
    path = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))[-1]
    out = []
    with warnings.catch_warnings():
        # ProfileData's stats type warns about its own __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        out.append(types.SimpleNamespace(
                            name=e.name, start=e.start_ns,
                            end=e.start_ns + e.duration_ns,
                            batch=dict(e.stats).get("batch")))
    return sorted(out, key=lambda s: s.start)


@pytest.fixture(scope="module")
def served(split, xs, tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace")
    srv = _server(split)
    with jax.profiler.trace(str(log_dir)):
        tickets = _prefill(srv, xs[:6])
        with srv:
            tickets += [srv.submit("t0", x) for x in xs[6:]]
            outs = [t.result(timeout=60.0) for t in tickets]
            time.sleep(0.3)                 # nothing left: the scheduler idles
    return types.SimpleNamespace(tickets=tickets, outs=np.stack(outs),
                                 stats=srv.session("t0").stats(),
                                 spans=_spans(log_dir))


def test_every_span_appears(served):
    assert {s.name for s in served.spans} == set(SPANS)


def test_one_of_each_dispatch_span_per_batch(served):
    n = served.stats.batches
    assert 1 < n < len(served.tickets)      # multi-request batches formed
    for name in ("session.dispatch", "session.ready", "session.fetch",
                 "serve.fulfill"):
        assert sum(s.name == name for s in served.spans) == n, name
    assert all(s.batch is None for s in served.spans
               if s.name == "serve.idle")


def test_span_batch_ids_are_the_tickets(served):
    ids = {t.batch for t in served.tickets}
    assert None not in ids and len(ids) == served.stats.batches
    assert {s.batch for s in served.spans if s.batch is not None} == ids


def test_a_dispatch_opens_its_spans_in_order(served):
    """Per batch: form, dispatch, ready, fetch, fulfill, one after the
    other and never overlapping (the idle attribution adds them up)."""
    for b in {t.batch for t in served.tickets}:
        own = [s for s in served.spans if s.batch == b]
        assert [s.name for s in own] == list(PER_DISPATCH)
        for a, c in zip(own, own[1:]):
            assert a.end <= c.start


def test_ticket_stamps_are_ordered_and_shared_per_batch(served):
    by_batch = {}
    for t in served.tickets:
        assert t.queued_at <= t.dispatched_at <= t.completed_at
        by_batch.setdefault(t.batch, set()).add(t.dispatched_at)
    assert all(len(v) == 1 for v in by_batch.values())


def test_traced_outputs_are_bit_identical(served, ref):
    assert np.array_equal(served.outs, ref)


def test_session_flush_stamps(split, xs):
    sess = Session(split, precision="int8", seed=0, max_batch=2,
                   buckets=(1, 2))
    tickets = [sess.submit(x) for x in xs[:5]]
    for t in tickets:
        assert np.isfinite(t.queued_at)
        assert np.isnan(t.dispatched_at) and t.batch is None
    assert sess.flush() == 5
    assert [t.batch for t in tickets] == [0, 0, 1, 1, 2]   # chunks of 2
    for t in tickets:
        assert t.queued_at <= t.dispatched_at <= t.completed_at
    assert tickets[0].dispatched_at == tickets[1].dispatched_at
    assert tickets[1].dispatched_at < tickets[2].dispatched_at


def test_untraced_serving_is_bit_identical(split, xs, ref):
    srv = _server(split)
    with srv:
        outs = [srv.run("t0", x, timeout=60.0) for x in xs[:3]]
        tickets = [srv.submit("t0", x) for x in xs[3:]]
        outs += [t.result(timeout=60.0) for t in tickets]
    assert np.array_equal(np.stack(outs), ref)
    sess = Session(split, precision="int8", seed=0, max_batch=4)
    tickets = [sess.submit(x) for x in xs]
    sess.flush()
    assert np.array_equal(np.stack([t.result() for t in tickets]), ref)


class _NeverReady:
    def block_until_ready(self):
        raise RuntimeError("device lost")


@pytest.mark.parametrize("where", ["dispatch", "wait"])
def test_failing_dispatch_rejects_with_fulfill_closed(split, xs, tmp_path,
                                                      where):
    srv = _server(split)
    sess = srv.session("t0")
    boom = RuntimeError("device lost")

    def failing(batch, mode):
        if where == "dispatch":
            raise boom
        return _NeverReady()

    sess.engine.run_batch_async = failing
    with jax.profiler.trace(str(tmp_path)):
        tickets = _prefill(srv, xs[:3])
        with srv:
            for t in tickets:
                with pytest.raises(RuntimeError, match="device lost"):
                    t.result(timeout=60.0)
            assert srv.running
    spans = _spans(tmp_path)
    ids = {t.batch for t in tickets}
    assert None not in ids
    assert all(t.dispatched_at <= t.completed_at for t in tickets)
    fulfill = [s for s in spans if s.name == "serve.fulfill"]
    assert {s.batch for s in fulfill} == ids and len(fulfill) == len(ids)
    assert sum(s.name == "session.dispatch" for s in spans) == len(ids)
    assert sum(s.name == "session.ready" for s in spans) == (
        len(ids) if where == "wait" else 0)
