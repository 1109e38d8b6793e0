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
from repro.kernels.qgemm.ref import qgemm_ref


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


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
