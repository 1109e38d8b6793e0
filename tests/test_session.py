"""Session (micro-batched serving) tests: bucket-padded ``submit_many`` must
be bit-identical to ``run_batch`` in int8, the submit/flush queue must
fulfill tickets in order, compiled buckets must be reusable across shapes
(the UnexpectedTracerError regression), and stats must account every
request/pad."""
import numpy as np
import pytest

from conftest import small_cnn
from repro.api import Cluster, Objective, Planner, Session
from repro.core import (CompiledSplitExecutor, SplitExecutor,
                        calibrate_scales, quantize_model, reference_forward,
                        split_model)


@pytest.fixture(scope="module")
def model():
    return small_cnn()


@pytest.fixture(scope="module")
def qmodel(model):
    rng = np.random.default_rng(0)
    calib = [rng.standard_normal(model.input_shape).astype(np.float32)
             for _ in range(3)]
    scales = calibrate_scales(
        model, calib,
        lambda m, x: reference_forward(m, x, collect_activations=True)[1])
    return quantize_model(model, scales)


@pytest.fixture(scope="module")
def plan(model):
    return Planner(model, Cluster.heterogeneous_demo(3)).plan(
        Objective(ram_cap_bytes=512 * 1024))


@pytest.fixture(scope="module")
def xs(model):
    rng = np.random.default_rng(1)
    return np.stack([rng.standard_normal(model.input_shape).astype(np.float32)
                     for _ in range(7)])


class TestSessionServing:
    def test_submit_many_matches_run_batch_bitexact_int8(self, plan, qmodel, xs):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=4)
        out = session.submit_many(xs)          # 7 requests -> buckets 4 + 4(pad 1)
        ref = CompiledSplitExecutor(plan.split, qmodel).run_batch(
            xs, mode="int8")
        assert out.dtype == ref.dtype == np.int8
        assert np.array_equal(out, ref)

    def test_run_matches_eager_oracle_int8(self, plan, qmodel, xs):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=2)
        eager = SplitExecutor(plan.split, qmodel)
        assert np.array_equal(session.run(xs[0]),
                              eager.run(xs[0], mode="int8"))

    def test_float_precision_close_to_reference(self, plan, model, xs):
        session = Session(plan, precision="float", max_batch=4)
        out = session.submit_many(xs[:3])
        for i in range(3):
            ref = reference_forward(model, xs[i])
            assert np.max(np.abs(out[i] - ref)) < 1e-4

    def test_bucket_reuse_across_shapes(self, plan, qmodel, xs):
        """Regression: the compiled engine must survive serving at several
        batch shapes (constants created inside one trace used to leak into
        the next as tracers)."""
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=4,
                          buckets=(1, 2, 4))
        a = session.submit_many(xs[:1])     # bucket 1
        b = session.submit_many(xs[:3])     # bucket 4 (pad 1)
        c = session.submit_many(xs[:2])     # bucket 2
        ref = CompiledSplitExecutor(plan.split, qmodel).run_batch(
            xs[:3], mode="int8")
        assert np.array_equal(a[0], ref[0])
        assert np.array_equal(b, ref)
        assert np.array_equal(c, ref[:2])

    def test_submit_flush_tickets(self, plan, qmodel, xs):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=4)
        tickets = [session.submit(x) for x in xs[:3]]
        assert session.n_pending == 3
        assert not tickets[0].done()
        served = session.flush()
        assert served == 3 and session.n_pending == 0
        ref = CompiledSplitExecutor(plan.split, qmodel).run_batch(
            xs[:3], mode="int8")
        for t, r in zip(tickets, ref):
            assert t.done() and np.array_equal(t.result(), r)

    def test_ticket_result_flushes_on_demand(self, plan, qmodel, xs):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=4)
        t = session.submit(xs[0])
        ref = CompiledSplitExecutor(plan.split, qmodel).run_batch(
            xs[:1], mode="int8")[0]
        assert np.array_equal(t.result(), ref)   # implicit flush
        assert session.n_pending == 0

    def test_stats_account_requests_and_padding(self, plan, qmodel, xs):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=4,
                          buckets=(1, 2, 4))
        session.submit_many(xs)                  # 7 -> dispatches of 4 and 4(pad 1)
        s = session.stats()
        assert s.requests == 7
        assert s.batches == 2
        assert s.padded == 1
        assert s.wall_s > 0 and s.throughput_rps > 0
        assert sum(s.per_bucket.values()) == s.batches
        # deployment context flows from the plan into the stats
        assert s.transport == plan.transport
        assert s.predicted_overlap_saved_s == plan.overlap_saved_s

    def test_bare_splitplan_session_defaults_to_serial(self, model, qmodel):
        session = Session(split_model(model, np.ones(2)), precision="int8",
                          qmodel=qmodel)
        s = session.stats()
        assert s.transport == "serial"
        assert s.predicted_overlap_saved_s == 0.0

    def test_auto_calibration_path(self, plan, xs):
        """int8 without an explicit qmodel: Session calibrates itself and
        still serves deterministically."""
        s1 = Session(plan, precision="int8", seed=7)
        s2 = Session(plan, precision="int8", seed=7)
        assert np.array_equal(s1.run(xs[0]), s2.run(xs[0]))


class TestSessionValidation:
    def test_rejects_bad_precision(self, plan):
        with pytest.raises(ValueError, match="precision"):
            Session(plan, precision="fp16")

    def test_rejects_bad_shapes(self, plan, qmodel, xs):
        session = Session(plan, precision="int8", qmodel=qmodel)
        with pytest.raises(ValueError, match="shape"):
            session.run(xs[0][:, :4, :])
        with pytest.raises(ValueError, match="shape"):
            session.submit_many(xs[:, :, :4, :])

    def test_rejects_bad_plan_type(self):
        with pytest.raises(TypeError):
            Session(object(), precision="float")

    def test_accepts_bare_split_plan(self, model, qmodel, xs):
        """Benchmarks/tests can wrap a core SplitPlan directly."""
        split = split_model(model, np.asarray([2.0, 1.0]))
        session = Session(split, precision="int8", qmodel=qmodel, max_batch=2)
        ref = CompiledSplitExecutor(split, qmodel).run_batch(xs[:2],
                                                             mode="int8")
        assert np.array_equal(session.submit_many(xs[:2]), ref)

    def test_empty_batch_keeps_output_shape_and_dtype(self, plan, qmodel,
                                                      model, xs):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=2)
        empty = session.submit_many(xs[:0])
        assert empty.shape == (0, *model.out_shape)
        assert empty.dtype == np.int8
        # concatenates cleanly with real outputs
        real = session.submit_many(xs[:1])
        assert np.concatenate([empty, real]).shape == (1, *model.out_shape)
        sf = Session(plan, precision="float", max_batch=2)
        assert sf.submit_many(xs[:0]).dtype == np.float32

    def test_warmup_compiles_buckets(self, plan, qmodel):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=2,
                          buckets=(1, 2))
        session.warmup()
        assert session.stats().requests == 0  # warmup is not traffic


class TestTicketHardening:
    def test_result_with_timeout_fulfills(self, plan, qmodel, xs):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=4)
        t = session.submit(xs[0])
        ref = CompiledSplitExecutor(plan.split, qmodel).run_batch(
            xs[:1], mode="int8")[0]
        assert np.array_equal(t.result(timeout=60.0), ref)
        assert t.exception() is None
        assert t.completed_at > 0          # fulfillment stamp for latency

    def test_detached_ticket_timeout_raises(self):
        from repro.api import Ticket
        t = Ticket()                        # no session to flush
        with pytest.raises(TimeoutError, match="unfulfilled"):
            t.result(timeout=0.02)
        assert not t.done()
        assert np.isnan(t.completed_at)     # still pending: no stamp

    def test_poisoned_dispatch_rejects_all_pending_tickets(
            self, plan, qmodel, xs, monkeypatch):
        """Regression: a raising dispatch mid-batch must reject every ticket
        of that flush with the exception — callers blocked on ``result()``
        get the error instead of hanging forever."""
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=4)
        tickets = [session.submit(x) for x in xs[:3]]
        boom = RuntimeError("poisoned input blew up the batch")
        monkeypatch.setattr(session.engine, "run_batch_async",
                            lambda *a, **k: (_ for _ in ()).throw(boom))
        with pytest.raises(RuntimeError, match="poisoned"):
            session.flush()
        for t in tickets:
            assert t.done()
            assert t.exception() is boom
            with pytest.raises(RuntimeError, match="poisoned"):
                t.result(timeout=1.0)
        # the queue was consumed, not wedged: serving resumes after the fix
        monkeypatch.undo()
        assert session.n_pending == 0
        good = session.submit(xs[0])
        ref = CompiledSplitExecutor(plan.split, qmodel).run_batch(
            xs[:1], mode="int8")[0]
        assert np.array_equal(good.result(timeout=60.0), ref)

    def test_rolling_percentile_stats_fields(self, plan, qmodel, xs):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=4,
                          buckets=(1, 2, 4))
        s0 = session.stats()
        assert np.isnan(s0.latency_p50_s)
        assert s0.per_bucket_p50_s == {}
        session.submit_many(xs)             # 7 -> buckets 4 + 4(pad 1)
        s = session.stats()
        assert s.latency_p50_s > 0
        assert set(s.per_bucket_p50_s) == set(s.per_bucket) == {4}
        assert s.per_bucket_p50_s[4] > 0
        # the same rolling window answers the admission-control query
        assert session.dispatch_latency_s(bucket=4) == s.per_bucket_p50_s[4]
        assert np.isnan(session.dispatch_latency_s(bucket=2))


class TestBucketPaddingEdgeCases:
    def test_flush_of_more_than_max_bucket_chunks(self, plan, qmodel, xs):
        """A backlog larger than the biggest bucket flushes in max_batch
        chunks — every ticket fulfilled, order preserved."""
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=2,
                          buckets=(1, 2))
        tickets = [session.submit(x) for x in xs[:5]]   # 5 > max bucket 2
        assert session.flush() == 5
        ref = CompiledSplitExecutor(plan.split, qmodel).run_batch(
            xs[:5], mode="int8")
        for t, r in zip(tickets, ref):
            assert np.array_equal(t.result(), r)
        s = session.stats()
        assert s.batches == 3                     # 2 + 2 + 1(pad to bucket 1)
        assert s.per_bucket == {2: 2, 1: 1}

    def test_empty_flush_is_a_noop(self, plan, qmodel):
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=2)
        assert session.flush() == 0
        assert session.stats().batches == 0

    def test_submit_during_dispatch_lands_in_next_flush(self, plan, qmodel,
                                                        xs, monkeypatch):
        """Interleaved submit/flush: a request submitted while a dispatch is
        executing is untouched by that flush and served by the next one."""
        session = Session(plan, precision="int8", qmodel=qmodel, max_batch=4)
        first = [session.submit(x) for x in xs[:2]]
        real = session.engine.run_batch_async
        late: list = []

        def submit_mid_dispatch(batch, mode):
            if not late:                      # only on the first dispatch
                late.append(session.submit(xs[2]))
            return real(batch, mode=mode)

        monkeypatch.setattr(session.engine, "run_batch_async",
                            submit_mid_dispatch)
        assert session.flush() == 2           # the late submit is NOT in it
        assert all(t.done() for t in first)
        assert not late[0].done()
        assert session.n_pending == 1
        assert session.flush() == 1           # ...but the next flush has it
        ref = CompiledSplitExecutor(plan.split, qmodel).run_batch(
            xs[:3], mode="int8")
        for t, r in zip(first + late, ref):
            assert np.array_equal(t.result(), r)
