"""Pallas kernel validation: shape/dtype sweeps, assert_allclose vs the
ref.py pure-jnp oracles (interpret=True on CPU; TPU is the target)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn.ops import flash_decode, flash_decode_ref
from repro.kernels.dwconv.dwconv import dwconv3x3_bands, dwconv_blocks
from repro.kernels.dwconv.ops import dwconv, dwconv_bands, dwconv_ref
from repro.kernels.dwconv.ref import dwconv_valid_ref
from repro.kernels.qgemm.ops import (qconv2d, qconv2d_ref, qgemm_padded)
from repro.kernels.qgemm.qgemm import (VMEM_BUDGET, qgemm, qgemm_blocks,
                                       step_vmem_bytes)
from repro.kernels.qgemm.ref import qgemm_ref

from qgemm_calls import PLANS as QGEMM_PLANS
from qgemm_calls import eqns as _eqns
from qgemm_calls import qgemm_calls


def _gemm_operands(rng, shape, k, n, bias):
    """int8 operands, and a scale that spreads the outputs over the
    activation's bends and the int8 range rather than saturating them."""
    x = rng.integers(-127, 128, shape).astype(np.int8)
    w = rng.integers(-127, 128, (k, n)).astype(np.int8)
    s = (rng.uniform(0.5, 1.5, n) * 3 / (5376 * np.sqrt(k))
         ).astype(np.float32)
    b = (rng.integers(-5000, 5000, n).astype(np.int32) if bias == "int"
         else rng.uniform(-1, 1, n).astype(np.float32))
    return x, w, s, b


@pytest.fixture
def rng():
    return np.random.default_rng(42)


class TestQGEMM:
    @pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                       (64, 200, 72), (300, 128, 513)])
    @pytest.mark.parametrize("act,osc", [(None, None), ("relu", None),
                                         ("relu6", 0.05), (None, 0.02)])
    def test_sweep_vs_ref(self, rng, m, k, n, act, osc):
        x = rng.integers(-127, 128, (m, k)).astype(np.int8)
        w = rng.integers(-127, 128, (k, n)).astype(np.int8)
        s = rng.uniform(1e-3, 1e-2, n).astype(np.float32)
        b = rng.uniform(-1, 1, n).astype(np.float32)
        got = np.asarray(qgemm_padded(x, w, s, b, activation=act,
                                      out_scale=osc), np.float32)
        exp = np.asarray(qgemm_ref(x, w, s, b, activation=act,
                                   out_scale=osc), np.float32)
        if osc is not None:
            assert np.max(np.abs(got - exp)) <= 1     # requant ulp
        else:
            np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-3)

    def test_int32_accumulation_exact(self, rng):
        """No epilogue scaling: int32 accumulation must be bit-exact."""
        x = rng.integers(-127, 128, (128, 512)).astype(np.int8)
        w = rng.integers(-127, 128, (512, 128)).astype(np.int8)
        ones = np.ones(128, np.float32)
        zeros = np.zeros(128, np.float32)
        got = np.asarray(qgemm_padded(x, w, ones, zeros))
        exp = x.astype(np.int64) @ w.astype(np.int64)
        np.testing.assert_array_equal(got.astype(np.int64), exp)

    # (batch, M, K, N, bias): shapes of the plans' GEMMs (M of one sample:
    # 1 for a linear, 49 a 7x7 map, 3136 a 56x56 one, 19712 MobileNetV3's
    # banded stem); the largest M only at batch 1, to keep interpret mode fast
    @pytest.mark.parametrize("batch,m,k,n,bias", [
        (1, 19712, 27, 16, "int"),
        (1, 1, 1280, 1280, "float"),
        (8, 49, 1280, 16, "int"),
        (8, 3136, 96, 144, "float"),
        (8, 1, 96, 1280, "int"),
        (32, 1, 1280, 144, "int"),
        (32, 49, 96, 1280, "float"),
        (32, 3136, 27, 16, "int"),
        (32, 49, 27, 144, "float"),
    ])
    def test_qgemm_vmapped_bit_exact(self, rng, batch, m, k, n, bias):
        """The batch folded into M equals every sample through the oracle:
        bit for bit with the int32 bias; within one output step with a
        float bias, whose ``acc * scale + bias`` may contract to an FMA on
        one side only (as in ``test_sweep_vs_ref``)."""
        x, w, s, b = _gemm_operands(rng, (batch, m, k), k, n, bias)
        fn = jax.vmap(functools.partial(qgemm_padded, activation="hswish",
                                        out_scale=0.05),
                      in_axes=(0, None, None, None))
        got = np.asarray(fn(x, w, s, b))
        exp = np.asarray(qgemm_ref(x.reshape(-1, k), w, s, b,
                                   activation="hswish", out_scale=0.05)
                         ).reshape(batch, m, n)
        assert got.shape == exp.shape and got.dtype == np.int8
        if bias == "int":
            np.testing.assert_array_equal(got, exp)
        else:
            assert np.max(np.abs(got.astype(np.int32) - exp)) <= 1

    @pytest.mark.parametrize("batch,m,k,n", [
        (32, 19712, 27, 16), (32, 1, 1280, 1000), (8, 49, 96, 144),
        (1, 3136, 24, 144)])
    def test_qgemm_vmap_folds_into_one_call(self, batch, m, k, n):
        """Under vmap the GEMM is one pallas_call over all B*M rows, with
        the grid of the blocks ``qgemm_blocks`` picks and no batch axis."""
        fn = jax.vmap(functools.partial(qgemm_padded, out_scale=0.05),
                      in_axes=(0, None, None, None))
        jaxpr = jax.make_jaxpr(fn)(
            jnp.zeros((batch, m, k), jnp.int8), jnp.zeros((k, n), jnp.int8),
            jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.int32))
        grids = [e.params["grid_mapping"].grid
                 for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
        blk = qgemm_blocks(batch * m, k, n)
        assert grids == [(-(-batch * m // blk.m), -(-n // 128) * 128 // blk.n,
                          -(-k // 128) * 128 // blk.k)]

    def test_qgemm_vmap_over_weights(self, rng):
        """Batched weights (the outer vmap, over x and w) fall back to
        vmapping the call; the inner vmap, over x alone, still folds."""
        x, w, s, b = _gemm_operands(rng, (2, 3, 40, 50), 50, 24, "int")
        ws = rng.integers(-127, 128, (2, 50, 24)).astype(np.int8)
        call = functools.partial(qgemm_padded, activation="relu6",
                                 out_scale=0.05)
        nested = jax.vmap(jax.vmap(call, in_axes=(0, None, None, None)),
                          in_axes=(0, 0, None, None))
        got = np.asarray(nested(x, ws, s, b))
        for i in range(2):
            exp = qgemm_ref(x[i].reshape(-1, 50), ws[i], s, b,
                            activation="relu6", out_scale=0.05)
            np.testing.assert_array_equal(
                got[i], np.asarray(exp).reshape(3, 40, 24))

    def test_k_split_over_the_grid(self, rng):
        """Explicit blocks narrower than K split it over the grid's last
        axis, the int32 partial sums kept across steps; M not a multiple of
        the block leaves an overhanging last block, and a block of rows not
        a multiple of the in-kernel chunk leaves a shorter last chunk."""
        x, w, s, b = _gemm_operands(rng, (700, 300), 300, 130, "int")
        got = qgemm(x, w, s, b, activation="hswish", out_scale=0.05,
                    block_m=640, block_n=256, block_k=128)
        exp = qgemm_ref(x, w, s, b, activation="hswish", out_scale=0.05)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))

    @pytest.mark.parametrize("plan", sorted(QGEMM_PLANS))
    def test_qgemm_blocks_within_budget(self, plan):
        """Every GEMM of the committed plans, at buckets 1 and 32: whole
        blocks of int8 row tiles over the padded shape, a VMEM estimate
        within the budget, and the kernel's grid is those blocks'."""
        for batch in (1, 32):
            calls = qgemm_calls(plan, batch)
            assert calls
            for c in calls:
                blk = qgemm_blocks(c.m, c.k, c.n)
                kp, np_ = -(-c.k // 128) * 128, -(-c.n // 128) * 128
                assert blk.m % 32 == 0 and kp % blk.k == 0 and np_ % blk.n == 0
                assert step_vmem_bytes(blk.m, blk.k, blk.n,
                                       kp // blk.k) <= VMEM_BUDGET
                assert c.grid == (-(-c.m // blk.m), np_ // blk.n, kp // blk.k)
                # M pads by less than one int8 row tile a block
                assert c.grid[0] * blk.m - c.m < 32 * c.grid[0]

    @pytest.mark.parametrize("stride", [(1, 1), (2, 2)])
    def test_qconv2d(self, rng, stride):
        x = rng.integers(-127, 128, (16, 14, 14)).astype(np.int8)
        w = rng.integers(-127, 128, (24, 16, 3, 3)).astype(np.int8)
        s = rng.uniform(1e-3, 1e-2, 24).astype(np.float32)
        b = rng.uniform(-1, 1, 24).astype(np.float32)
        got = qconv2d(x, w, s, b, stride=stride, padding=(1, 1),
                      activation="relu6", out_scale=0.05)
        exp = qconv2d_ref(x, w, s, b, stride=stride, padding=(1, 1),
                          activation="relu6", out_scale=0.05)
        assert np.max(np.abs(np.asarray(got, np.int32)
                             - np.asarray(exp, np.int32))) <= 1

    def test_qconv_matches_float_conv(self, rng):
        """End-to-end quantized conv tracks the float conv (corr > 0.99)."""
        import jax
        import jax.numpy as jnp
        xf = rng.standard_normal((8, 10, 10)).astype(np.float32)
        wf = (rng.standard_normal((12, 8, 3, 3)) * 0.1).astype(np.float32)
        sx = np.abs(xf).max() / 127
        x_q = np.clip(np.round(xf / sx), -127, 127).astype(np.int8)
        sw = np.abs(wf).max(axis=(1, 2, 3)) / 127
        w_q = np.clip(np.round(wf / sw[:, None, None, None]), -127, 127).astype(np.int8)
        got = np.asarray(qconv2d(x_q, w_q, (sx * sw).astype(np.float32),
                                 np.zeros(12, np.float32), padding=(1, 1)))
        ref = jax.lax.conv_general_dilated(
            jnp.asarray(xf)[None], jnp.asarray(wf), (1, 1),
            [(1, 1), (1, 1)], dimension_numbers=("NCHW", "OIHW", "NCHW"))[0]
        corr = np.corrcoef(got.ravel(), np.asarray(ref).ravel())[0, 1]
        assert corr > 0.99


class TestDWConv:
    @pytest.mark.parametrize("c,hw", [(8, 16), (19, 12), (32, 7), (50, 9),
                                      (960, 4)])
    @pytest.mark.parametrize("stride", [1, 2])
    def test_sweep_vs_ref(self, rng, c, hw, stride):
        x = rng.integers(-127, 128, (c, hw, hw)).astype(np.int8)
        w = rng.integers(-127, 128, (c, 3, 3)).astype(np.int8)
        s = rng.uniform(1e-3, 1e-2, c).astype(np.float32)
        b = rng.uniform(-1, 1, c).astype(np.float32)
        got = dwconv(x, w, s, b, stride=stride, activation="relu6",
                     out_scale=0.05)
        exp = dwconv_ref(x, w, s, b, stride=stride, activation="relu6",
                         out_scale=0.05)
        assert got.shape == exp.shape
        assert np.max(np.abs(np.asarray(got, np.int32)
                             - np.asarray(exp, np.int32))) <= 1

    @pytest.mark.parametrize("c,hw", [(8, 16), (24, 13), (32, 7), (29, 14),
                                      (246, 4)])
    @pytest.mark.parametrize("stride", [1, 2])
    def test_int_bias_bit_exact(self, rng, c, hw, stride):
        """The executors' contract: int32 bias + multiply-only epilogue
        equals the oracle exactly, odd maps and stride-2 phases included."""
        x = rng.integers(-127, 128, (c, hw, hw)).astype(np.int8)
        w = rng.integers(-127, 128, (c, 3, 3)).astype(np.int8)
        s = rng.uniform(1e-4, 1e-3, c).astype(np.float32)
        b = rng.integers(-5000, 5000, c).astype(np.int32)
        got = dwconv(x, w, s, b, stride=stride, activation="relu6",
                     out_scale=0.05)
        exp = dwconv_ref(x, w, s, b, stride=stride, activation="relu6",
                         out_scale=0.05)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))

    @pytest.mark.parametrize("stride", [1, 2])
    def test_bands_bit_exact(self, rng, stride):
        """A band stack (bands on the grid) equals each band alone."""
        bands, c, rows, cols = 3, 19, 2 * 3 + 3, 12
        x = rng.integers(-127, 128, (bands, c, rows, cols)).astype(np.int8)
        w = rng.integers(-127, 128, (c, 3, 3)).astype(np.int8)
        s = rng.uniform(1e-4, 1e-3, c).astype(np.float32)
        b = rng.integers(-5000, 5000, c).astype(np.int32)
        got = np.asarray(dwconv_bands(x, w, s, b, stride=stride,
                                      activation="relu6", out_scale=0.05))
        for i in range(bands):
            exp = dwconv_valid_ref(x[i], w, s, b, stride=stride,
                                   activation="relu6", out_scale=0.05)
            np.testing.assert_array_equal(got[i], np.asarray(exp))

    # (batch, bands, C, rows, cols, stride, bias): run_batch vmaps the
    # kernel over the batch; band stacks and channel counts of the plans
    @pytest.mark.parametrize("batch,bands,c,rows,cols,stride,bias", [
        (1, 7, 29, 9, 10, 1, "int"),
        (1, 4, 960, 3, 6, 1, "float"),
        (1, 1, 50, 16, 16, 2, "int"),
        (8, 7, 20, 5, 12, 2, "int"),
        (8, 4, 50, 4, 9, 1, "float"),
        (8, 1, 246, 6, 6, 1, "int"),
        (8, 7, 960, 3, 6, 2, "float"),
        (32, 1, 246, 6, 6, 1, "int"),
        (32, 1, 29, 9, 9, 2, "float"),
        (32, 4, 960, 3, 6, 1, "int"),
        (32, 7, 20, 13, 14, 2, "int"),
    ])
    def test_vmapped_stack_bit_exact(self, rng, batch, bands, c, rows, cols,
                                     stride, bias):
        """The batch folded into the stack equals every sample's every band
        alone through the oracle: bit for bit with the int32 bias; within
        one output step with a float bias, whose ``acc * scale + bias`` may
        contract to an FMA on one side only (as in ``test_sweep_vs_ref``)."""
        x = rng.integers(-127, 128, (batch, bands, c, rows, cols)
                         ).astype(np.int8)
        w = rng.integers(-127, 128, (c, 3, 3)).astype(np.int8)
        s = rng.uniform(1e-4, 1e-3, c).astype(np.float32)
        b = (rng.integers(-5000, 5000, c).astype(np.int32) if bias == "int"
             else rng.uniform(-1, 1, c).astype(np.float32))
        fn = jax.vmap(functools.partial(dwconv3x3_bands, stride=stride,
                                        activation="relu6", out_scale=0.05),
                      in_axes=(0, None, None, None))
        got = np.asarray(fn(x, w, s, b))
        for i in range(batch):
            for j in range(bands):
                exp = np.asarray(dwconv_valid_ref(
                    x[i, j], w, s, b, stride=stride, activation="relu6",
                    out_scale=0.05))
                if bias == "int":
                    np.testing.assert_array_equal(got[i, j], exp)
                else:
                    assert np.max(np.abs(got[i, j].astype(np.int32)
                                         - exp)) <= 1

    @pytest.mark.parametrize("stride", [1, 2])
    def test_channel_blocks_narrower_than_c(self, rng, monkeypatch, stride):
        """Where one entry with all channels would pass the VMEM budget, the
        channels split into blocks of 128 lanes and C is zero-padded to a
        whole block (shown here under a small budget)."""
        from repro.kernels.dwconv import dwconv as kernel
        monkeypatch.setattr(kernel, "VMEM_BUDGET", 2**19)
        bands, c, rows, cols = 3, 300, 7 + stride, 10
        assert dwconv_blocks(bands, rows, cols, c, stride).channels == 128
        x = rng.integers(-127, 128, (bands, c, rows, cols)).astype(np.int8)
        w = rng.integers(-127, 128, (c, 3, 3)).astype(np.int8)
        s = rng.uniform(1e-4, 1e-3, c).astype(np.float32)
        b = rng.integers(-5000, 5000, c).astype(np.int32)
        got = np.asarray(dwconv3x3_bands(x, w, s, b, stride=stride,
                                         activation="relu6", out_scale=0.05))
        for i in range(bands):
            exp = dwconv_valid_ref(x[i], w, s, b, stride=stride,
                                   activation="relu6", out_scale=0.05)
            np.testing.assert_array_equal(got[i], np.asarray(exp))

    @pytest.mark.parametrize("batch,bands,c,rows,cols,stride", [
        (8, 7, 32, 15, 58, 1), (32, 1, 246, 6, 6, 1), (8, 4, 960, 3, 6, 1)])
    def test_vmap_folds_into_one_call(self, batch, bands, c, rows, cols,
                                      stride):
        """Under vmap the kernel is one pallas_call whose grid is the
        blocks' (stack chunks, channel blocks), with no batch axis."""
        fn = jax.vmap(functools.partial(dwconv3x3_bands, stride=stride,
                                        out_scale=0.05),
                      in_axes=(0, None, None, None))
        jaxpr = jax.make_jaxpr(fn)(
            jnp.zeros((batch, bands, c, rows, cols), jnp.int8),
            jnp.zeros((c, 3, 3), jnp.int8), jnp.zeros(c, jnp.float32),
            jnp.zeros(c, jnp.int32))
        grids = [e.params["grid_mapping"].grid
                 for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
        blk = dwconv_blocks(batch * bands, rows, cols, c, stride)
        assert grids == [(batch * bands // blk.stack,
                          -(-c // blk.channels))]

    def test_vmap_over_weights_and_nested(self, rng):
        """Batched weights fall back to vmapping the call; a vmap of a vmap
        folds both batch axes into the stack."""
        x = rng.integers(-127, 128, (2, 3, 2, 11, 7, 8)).astype(np.int8)
        w = rng.integers(-127, 128, (2, 11, 3, 3)).astype(np.int8)
        s = rng.uniform(1e-4, 1e-3, 11).astype(np.float32)
        b = rng.integers(-5000, 5000, 11).astype(np.int32)
        call = functools.partial(dwconv3x3_bands, activation="relu6",
                                 out_scale=0.05)
        nested = jax.vmap(jax.vmap(call, in_axes=(0, None, None, None)),
                          in_axes=(0, 0, None, None))
        got = np.asarray(nested(x, w, s, b))
        for i in range(2):
            for k in range(3):
                for j in range(2):
                    exp = dwconv_valid_ref(x[i, k, j], w[i], s, b,
                                           activation="relu6", out_scale=0.05)
                    np.testing.assert_array_equal(got[i, k, j],
                                                  np.asarray(exp))

    def test_float_out(self, rng):
        x = rng.integers(-127, 128, (8, 10, 10)).astype(np.int8)
        w = rng.integers(-127, 128, (8, 3, 3)).astype(np.int8)
        s = np.ones(8, np.float32)
        b = np.zeros(8, np.float32)
        got = np.asarray(dwconv(x, w, s, b))
        exp = np.asarray(dwconv_ref(x, w, s, b))
        np.testing.assert_allclose(got, exp, rtol=1e-6, atol=1e-6)


class TestDecodeAttn:
    @pytest.mark.parametrize("b,k,g,hd,s,bs", [
        (2, 4, 5, 64, 1024, 256),
        (1, 8, 1, 128, 512, 512),
        (3, 2, 8, 32, 768, 128),
        (2, 1, 16, 64, 640, 128),
    ])
    def test_sweep_vs_ref(self, rng, b, k, g, hd, s, bs):
        q = rng.standard_normal((b, 1, k, g, hd)).astype(np.float32)
        ck = rng.standard_normal((b, s, k, hd)).astype(np.float32)
        cv = rng.standard_normal((b, s, k, hd)).astype(np.float32)
        lens = rng.integers(s // 2, s + 1, b).astype(np.int32)
        got = np.asarray(flash_decode(q, ck, cv, lens, block_s=bs))
        exp = np.asarray(flash_decode_ref(q, ck, cv, lens))
        np.testing.assert_allclose(got, exp, rtol=1e-5, atol=2e-5)

    def test_bf16_dtype(self, rng):
        import jax.numpy as jnp
        b, k, g, hd, s = 2, 2, 4, 64, 512
        q = jnp.asarray(rng.standard_normal((b, 1, k, g, hd)), jnp.bfloat16)
        ck = jnp.asarray(rng.standard_normal((b, s, k, hd)), jnp.bfloat16)
        cv = jnp.asarray(rng.standard_normal((b, s, k, hd)), jnp.bfloat16)
        lens = np.full(b, s, np.int32)
        got = np.asarray(flash_decode(q, ck, cv, lens, block_s=128),
                         np.float32)
        exp = np.asarray(flash_decode_ref(q, ck, cv, lens), np.float32)
        np.testing.assert_allclose(got, exp, rtol=3e-2, atol=3e-2)

    def test_length_masking(self, rng):
        """Slots beyond `lengths` must not influence the output."""
        b, k, g, hd, s = 1, 2, 2, 32, 256
        q = rng.standard_normal((b, 1, k, g, hd)).astype(np.float32)
        ck = rng.standard_normal((b, s, k, hd)).astype(np.float32)
        cv = rng.standard_normal((b, s, k, hd)).astype(np.float32)
        lens = np.array([100], np.int32)
        out1 = np.asarray(flash_decode(q, ck, cv, lens, block_s=64))
        ck2, cv2 = ck.copy(), cv.copy()
        ck2[:, 100:] = 99.0
        cv2[:, 100:] = -99.0
        out2 = np.asarray(flash_decode(q, ck2, cv2, lens, block_s=64))
        np.testing.assert_allclose(out1, out2, rtol=1e-6, atol=1e-6)
