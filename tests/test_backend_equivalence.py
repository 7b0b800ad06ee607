"""Backend-equivalence harness: pallas kernels vs the xla reference path.

The reference gradient-checks its cuDNN helper backend against the builtin
Java path on identical inputs (deeplearning4j-cuda/.../CuDNNGradientChecks
.java, TestConvolution.java — SURVEY.md §4 "backend-vs-backend
equivalence"). Here the hand-written Pallas TPU kernels are checked against
the lax.scan/autodiff implementations registered under backend="xla":
forward outputs AND every gradient must agree on identical inputs.

On CPU the Pallas kernels run in interpreter mode
(DL4J_TPU_PALLAS_INTERPRET=1); a TPU-gated subclass re-runs the same
checks compiled on real hardware when one is present.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import lstm as lstm_ops


def _data(t=5, b=8, n=128, dtype=jnp.float32, seed=0, masked=False):
    rng = np.random.default_rng(seed)
    xz = jnp.asarray(rng.normal(0, 0.5, (t, b, 4 * n)), dtype)
    h0 = jnp.asarray(rng.normal(0, 0.5, (b, n)), dtype)
    c0 = jnp.asarray(rng.normal(0, 0.5, (b, n)), dtype)
    Wh = jnp.asarray(rng.normal(0, 0.2, (n, 4 * n)), dtype)
    p = jnp.asarray(rng.normal(0, 0.2, (3, n)), dtype)
    if masked:
        m = (rng.random((t, b)) > 0.3).astype(np.float32)
        m[0] = 1.0  # keep step 0 alive for all examples
        mask = jnp.asarray(m, dtype)
    else:
        mask = jnp.ones((t, b), dtype)
    return xz, h0, c0, Wh, p, mask


def _loss_through(fn):
    def loss(xz, h0, c0, Wh, p, mask):
        y, hT, cT = fn(xz, h0, c0, Wh, p, mask)
        w = jnp.cos(jnp.arange(y.size, dtype=y.dtype)).reshape(y.shape)
        return (jnp.sum(y * w) + 2.0 * jnp.sum(jnp.sin(hT))
                + 0.5 * jnp.sum(cT * cT))
    return loss


class TestLstmBackendEquivalence:
    """Interpret-mode pallas vs xla on CPU (runs everywhere)."""

    def setup_method(self):
        os.environ["DL4J_TPU_PALLAS_INTERPRET"] = "1"

    def teardown_method(self):
        os.environ.pop("DL4J_TPU_PALLAS_INTERPRET", None)

    def _pallas(self, *args):
        return lstm_ops._lstm_seq_pallas(*args)

    def _xla(self, xz, h0, c0, Wh, p, mask):
        return lstm_ops.lstm_sequence_xla(xz, h0, c0, Wh, p, mask)

    @pytest.mark.parametrize("masked", [False, True])
    def test_forward_equivalence(self, masked):
        args = _data(masked=masked)
        y_p, hT_p, cT_p = self._pallas(*args)
        y_x, hT_x, cT_x = self._xla(*args)
        np.testing.assert_allclose(y_p, y_x, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(hT_p, hT_x, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(cT_p, cT_x, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("masked", [False, True])
    def test_gradient_equivalence(self, masked):
        # the CuDNNGradientChecks analogue: d/d{xz, h0, c0, Wh, p} must
        # match between the hand-written backward kernel and autodiff of
        # the scan path on identical inputs
        args = _data(t=4, b=8, n=128, masked=masked)
        g_p = jax.grad(_loss_through(self._pallas), argnums=(0, 1, 2, 3, 4))(
            *args)
        g_x = jax.grad(_loss_through(self._xla), argnums=(0, 1, 2, 3, 4))(
            *args)
        names = ["dxz", "dh0", "dc0", "dWh", "dp"]
        for name, gp, gx in zip(names, g_p, g_x):
            np.testing.assert_allclose(
                gp, gx, rtol=2e-4, atol=2e-4,
                err_msg=f"pallas/xla gradient mismatch for {name}")

    def test_wrapper_falls_back_when_unsupported(self):
        # unaligned hidden size -> the registered pallas backend must
        # delegate to xla (the cuDNN-absent fallback path)
        t, b, n = 3, 4, 24
        rng = np.random.default_rng(1)
        xz = jnp.asarray(rng.normal(0, 0.5, (t, b, 4 * n)), jnp.float32)
        h0 = jnp.zeros((b, n), jnp.float32)
        c0 = jnp.zeros((b, n), jnp.float32)
        Wh = jnp.asarray(rng.normal(0, 0.2, (n, 4 * n)), jnp.float32)
        p = jnp.zeros((3, n), jnp.float32)
        y_w, hT_w, cT_w = lstm_ops.lstm_sequence_pallas(
            xz, h0, c0, Wh, p, None)
        y_x, hT_x, cT_x = lstm_ops.lstm_sequence_xla(
            xz, h0, c0, Wh, p, None)
        np.testing.assert_allclose(y_w, y_x, rtol=1e-6)

    def test_registry_prefers_pallas(self):
        from deeplearning4j_tpu.ops import registry
        assert set(registry.backends("lstm_sequence")) == {"pallas", "xla"}
        assert registry.get("lstm_sequence") is lstm_ops.lstm_sequence_pallas


from deeplearning4j_tpu.ops import attention as attn_ops  # noqa: E402


def _attn_data(b=2, t=128, h=2, dh=128, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 0.5, (b, t, h, dh)), dtype)
    k = jnp.asarray(rng.normal(0, 0.5, (b, t, h, dh)), dtype)
    v = jnp.asarray(rng.normal(0, 0.5, (b, t, h, dh)), dtype)
    return q, k, v


def _attn_loss(fn):
    def loss(q, k, v):
        y = fn(q, k, v)
        w = jnp.cos(jnp.arange(y.size, dtype=y.dtype)).reshape(y.shape)
        return jnp.sum(y * w)
    return loss


class TestAttentionBackendEquivalence:
    """Interpret-mode flash attention vs the xla reference (runs on CPU)."""

    def setup_method(self):
        os.environ["DL4J_TPU_PALLAS_INTERPRET"] = "1"

    def teardown_method(self):
        os.environ.pop("DL4J_TPU_PALLAS_INTERPRET", None)

    @pytest.fixture(autouse=True)
    def _f32_matmuls(self):
        # these are f32-tolerance checks of dot lowerings against the
        # multiply+reduce reference; a TPU's default f32 dot is one bf16
        # pass (measured 3.7e-3 off on the v5e), so ask for real f32
        with jax.default_matmul_precision("highest"):
            yield

    def _pallas(self, q, k, v):
        return attn_ops._flash(q, k, v)

    def _xla(self, q, k, v):
        return attn_ops.causal_mha_xla(q, k, v)

    def test_forward_equivalence(self):
        q, k, v = _attn_data()
        assert attn_ops.attention_supported(q, k, v)
        np.testing.assert_allclose(self._pallas(q, k, v),
                                   self._xla(q, k, v),
                                   rtol=1e-5, atol=1e-5)

    def test_gradient_equivalence(self):
        # d/d{q, k, v} must match between the flash kernel's custom VJP
        # (recompute through the batched-dot formulation) and autodiff of
        # the exact mulsum path on identical inputs
        q, k, v = _attn_data(b=1, h=2)
        g_p = jax.grad(_attn_loss(self._pallas), argnums=(0, 1, 2))(q, k, v)
        g_x = jax.grad(_attn_loss(self._xla), argnums=(0, 1, 2))(q, k, v)
        for name, gp, gx in zip(("dq", "dk", "dv"), g_p, g_x):
            np.testing.assert_allclose(
                gp, gx, rtol=2e-4, atol=2e-4,
                err_msg=f"pallas/xla attention gradient mismatch for {name}")

    def test_xla_dot_matches_exact_within_tolerance(self):
        # the two xla lowerings (mulsum contract path vs batched GEMM)
        # agree to f32 reduction-order noise
        q, k, v = _attn_data(t=64, dh=32)
        np.testing.assert_allclose(
            attn_ops.causal_mha_xla_dot(q, k, v),
            attn_ops.causal_mha_xla(q, k, v), rtol=2e-6, atol=2e-6)

    def test_wrapper_falls_back_when_unsupported(self):
        # unaligned head dim / seq -> the registered pallas backend must
        # delegate to xla bit-for-bit (the cuDNN-absent fallback path)
        q, k, v = _attn_data(t=48, dh=64)
        assert not attn_ops.attention_supported(q, k, v)
        np.testing.assert_array_equal(
            np.asarray(attn_ops.causal_mha_pallas(q, k, v)),
            np.asarray(attn_ops.causal_mha_xla(q, k, v)))

    def test_decode_steps_stay_on_xla(self):
        # nonzero / traced q_start (incremental decode against the fixed
        # cache extent) is outside the flash gate by design
        q, k, v = _attn_data()
        assert not attn_ops.attention_supported(q, k, v, q_start=16)
        assert not attn_ops.attention_supported(
            q, k, v, q_start=jnp.zeros((2,), jnp.int32))

    def test_registry_backends_and_order(self):
        from deeplearning4j_tpu.ops import registry
        assert set(registry.backends("causal_mha")) == {
            "pallas", "xla", "xla_dot"}
        assert registry.get("causal_mha") is attn_ops.causal_mha_pallas
        assert registry.get("causal_mha", backend="xla") is \
            attn_ops.causal_mha_xla


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="needs a real TPU")
class TestAttentionBackendEquivalenceTPU:
    """Same checks, compiled on hardware, bf16 — the dtype the bench runs."""

    def test_forward_bf16(self):
        q, k, v = _attn_data(dtype=jnp.bfloat16)
        y_p = jax.jit(attn_ops._flash)(q, k, v)
        y_x = jax.jit(attn_ops.causal_mha_xla)(q, k, v)
        np.testing.assert_allclose(
            np.asarray(y_p, np.float32), np.asarray(y_x, np.float32),
            rtol=0.05, atol=0.05)

    def test_gradient_bf16_finite_and_close(self):
        q, k, v = _attn_data(b=1, dtype=jnp.bfloat16)
        g_p = jax.jit(jax.grad(_attn_loss(attn_ops._flash),
                               argnums=(0, 1)))(q, k, v)
        g_x = jax.jit(jax.grad(_attn_loss(attn_ops.causal_mha_xla),
                               argnums=(0, 1)))(q, k, v)
        for gp, gx in zip(g_p, g_x):
            gp = np.asarray(gp, np.float32)
            gx = np.asarray(gx, np.float32)
            assert np.all(np.isfinite(gp))
            scale = max(np.abs(gx).max(), 1e-3)
            assert np.abs(gp - gx).max() / scale < 0.1


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="needs a real TPU")
class TestLstmBackendEquivalenceTPU:
    """Same checks, compiled on hardware, bf16 — the dtype the bench runs."""

    def test_forward_bf16(self):
        args = _data(t=6, b=16, n=128, dtype=jnp.bfloat16)
        y_p, hT_p, cT_p = jax.jit(lstm_ops._lstm_seq_pallas)(*args)
        y_x, hT_x, cT_x = jax.jit(lstm_ops.lstm_sequence_xla)(*args)
        np.testing.assert_allclose(
            np.asarray(y_p, np.float32), np.asarray(y_x, np.float32),
            rtol=0.05, atol=0.05)

    def test_gradient_bf16_finite_and_close(self):
        args = _data(t=4, b=16, n=128, dtype=jnp.bfloat16, masked=True)
        g_p = jax.jit(jax.grad(_loss_through(lstm_ops._lstm_seq_pallas),
                               argnums=(0, 3)))(*args)
        g_x = jax.jit(jax.grad(_loss_through(lstm_ops.lstm_sequence_xla),
                               argnums=(0, 3)))(*args)
        for gp, gx in zip(g_p, g_x):
            gp = np.asarray(gp, np.float32)
            gx = np.asarray(gx, np.float32)
            assert np.all(np.isfinite(gp))
            scale = max(np.abs(gx).max(), 1e-3)
            assert np.abs(gp - gx).max() / scale < 0.1
