"""The compiled engine must match the eager oracle: float to tolerance,
int8 bit-for-bit (the epilogue contract in core.quantize), across worker
counts, heterogeneous ratings, batching, and the Pallas-kernel hot path."""
import numpy as np
import pytest

from repro.core import (CompiledSplitExecutor, SplitExecutor, calibrate_scales,
                        compile_shard_geometry, quantize_model,
                        reference_forward, split_model)
from repro.models import mobilenet_v2_smoke
from conftest import small_cnn

RATINGS = ([1.0], [1, 1, 1], np.ones(8), [3, 1, 2, 0.5])


def _acts_fn(model, x):
    return reference_forward(model, x, collect_activations=True)[1]


def _quantized(model, rng, shape, n_calib=3):
    calib = [rng.standard_normal(shape).astype(np.float32)
             for _ in range(n_calib)]
    scales = calibrate_scales(model, calib, _acts_fn)
    return quantize_model(model, scales), calib


class TestGeometry:
    def test_index_map_matches_worker_compute_decomposition(self):
        """The precomputed bbox map must be the contiguous run the executor
        slices, for every shard of every layer of the smoke model."""
        m = mobilenet_v2_smoke()
        for ratings in RATINGS:
            plan = split_model(m, ratings)
            for layer, split in zip(m.layers, plan.splits):
                geoms = compile_shard_geometry(layer, split)
                if layer.kind not in ("conv", "dwconv"):
                    assert all(g is None for g in geoms)
                    continue
                c_out, h_out, w_out = layer.out_shape
                hw = h_out * w_out
                for g, sh in zip(geoms, split.shards):
                    if sh.n_positions == 0:
                        assert g is None
                        continue
                    assert (g.start, g.stop) == (sh.start, sh.stop)
                    assert g.c_lo == sh.start // hw
                    assert g.c_hi == (sh.stop - 1) // hw
                    # index map is exactly the contiguous run at bbox_start
                    np.testing.assert_array_equal(
                        g.bbox_index,
                        np.arange(g.n_positions) + g.bbox_start)
                    # bbox holds the full shard
                    assert g.bbox_index[-1] < \
                        g.n_channels * g.n_rows * w_out


class TestFloatParity:
    def test_smoke_matches_eager_and_reference(self, rng):
        m = mobilenet_v2_smoke()
        x = rng.standard_normal((3, 32, 32)).astype(np.float32)
        ref = reference_forward(m, x)
        for ratings in RATINGS:
            plan = split_model(m, ratings)
            eager = SplitExecutor(plan).run(x)
            out = CompiledSplitExecutor(plan).run(x)
            np.testing.assert_allclose(out, eager, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_small_cnn_zero_rating_worker(self, rng):
        m = small_cnn()
        x = rng.standard_normal((3, 12, 12)).astype(np.float32)
        plan = split_model(m, [1.0, 0.0, 1.0])
        out = CompiledSplitExecutor(plan).run(x)
        np.testing.assert_allclose(out, reference_forward(m, x),
                                   rtol=1e-5, atol=1e-5)


class TestInt8Parity:
    def test_smoke_bit_exact_vs_eager(self, rng):
        """int8 is integer accumulation + a multiply-only f32 epilogue, so
        compiled must equal eager *exactly* for any split."""
        m = mobilenet_v2_smoke()
        qm, calib = _quantized(m, rng, (3, 32, 32))
        x = calib[0]
        for ratings in RATINGS:
            plan = split_model(m, ratings)
            eager = SplitExecutor(plan, qm).run(x, mode="int8")
            out = CompiledSplitExecutor(plan, qm).run(x, mode="int8")
            np.testing.assert_array_equal(out, eager)

    def test_int8_requires_qmodel(self):
        m = small_cnn()
        ex = CompiledSplitExecutor(split_model(m, [1, 1]))
        with pytest.raises(ValueError):
            ex.run(np.zeros((3, 12, 12), np.float32), mode="int8")


class TestPallasPath:
    """use_pallas=True routes dwconv through the Pallas dwconv3x3 kernel and
    conv/linear through qgemm (interpret mode on CPU).  The int32-bias
    epilogue keeps even this path bit-exact against the eager oracle."""

    def test_small_cnn_bit_exact(self, rng):
        m = small_cnn()
        qm, calib = _quantized(m, rng, (3, 12, 12))
        x = calib[0]
        plan = split_model(m, [1, 2, 1])
        eager = SplitExecutor(plan, qm).run(x, mode="int8")
        out = CompiledSplitExecutor(plan, qm, use_pallas=True,
                                    interpret=True).run(x, mode="int8")
        np.testing.assert_array_equal(out, eager)

    def test_batch_matches_singles(self, rng):
        m = small_cnn()
        qm, _ = _quantized(m, rng, (3, 12, 12))
        plan = split_model(m, [1, 1])
        ex = CompiledSplitExecutor(plan, qm, use_pallas=True, interpret=True)
        xs = np.stack([rng.standard_normal((3, 12, 12)).astype(np.float32)
                       for _ in range(3)])
        batch = ex.run_batch(xs, mode="int8")
        singles = np.stack([ex.run(xs[i], mode="int8") for i in range(3)])
        np.testing.assert_array_equal(batch, singles)


class TestBatching:
    def test_run_batch_equals_independent_runs(self, rng):
        m = mobilenet_v2_smoke()
        qm, _ = _quantized(m, rng, (3, 32, 32))
        plan = split_model(m, [2, 1, 1])
        ex = CompiledSplitExecutor(plan, qm)
        xs = np.stack([rng.standard_normal((3, 32, 32)).astype(np.float32)
                       for _ in range(8)])
        bq = ex.run_batch(xs, mode="int8")
        sq = np.stack([ex.run(xs[i], mode="int8") for i in range(8)])
        np.testing.assert_array_equal(bq, sq)
        # and against the eager oracle
        eq = np.stack([SplitExecutor(plan, qm).run(xs[i], mode="int8")
                       for i in range(8)])
        np.testing.assert_array_equal(bq, eq)

    def test_run_batch_float(self, rng):
        m = mobilenet_v2_smoke()
        plan = split_model(m, [1, 1, 1])
        ex = CompiledSplitExecutor(plan)
        xs = np.stack([rng.standard_normal((3, 32, 32)).astype(np.float32)
                       for _ in range(4)])
        bf = ex.run_batch(xs)
        sf = np.stack([ex.run(xs[i]) for i in range(4)])
        # The vmapped float conv reduces in a different order than the
        # single-sample one.  Float32 reordering error scales with the size
        # of the summed terms, not with each output, so a logit near zero
        # can differ by ~1e-6 absolute: bound it at rtol of the output scale.
        np.testing.assert_allclose(bf, sf, rtol=1e-5,
                                   atol=1e-5 * float(np.abs(sf).max()))

    def test_replicated_input_rows_identical(self, rng):
        """run_batch(stack([x]*B)) must produce B identical rows equal to
        run(x) — the vmapped trace is sample-independent."""
        m = mobilenet_v2_smoke()
        qm, _ = _quantized(m, rng, (3, 32, 32))
        ex = CompiledSplitExecutor(split_model(m, [1, 1]), qm)
        x = rng.standard_normal((3, 32, 32)).astype(np.float32)
        out = ex.run_batch(np.stack([x] * 5), mode="int8")
        single = ex.run(x, mode="int8")
        for b in range(5):
            np.testing.assert_array_equal(out[b], single)

    def test_warmup(self, rng):
        m = small_cnn()
        ex = CompiledSplitExecutor(split_model(m, [1, 1]))
        ex.warmup()
        ex.warmup(batch=2)
