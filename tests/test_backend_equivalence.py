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


def _data(t=5, b=8, n=128, dtype=jnp.float32, seed=0, masked=False,
          n_in=136):
    """(x, Wx, bias, h0, c0, Wh, p, mask). ``n_in`` 136 is wider than
    ``n`` = 128: the projection is a matmul ahead of the kernel; 128 and
    under, the forward kernel makes it."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1.0, (t, b, n_in)), dtype)
    Wx = jnp.asarray(rng.normal(0, 0.5 / np.sqrt(n_in), (n_in, 4 * n)),
                     dtype)
    bias = jnp.asarray(rng.normal(0, 0.3, (4 * n,)), dtype)
    h0 = jnp.asarray(rng.normal(0, 0.5, (b, n)), dtype)
    c0 = jnp.asarray(rng.normal(0, 0.5, (b, n)), dtype)
    Wh = jnp.asarray(rng.normal(0, 0.2, (n, 4 * n)), dtype)
    p = jnp.asarray(rng.normal(0, 0.2, (3, n)), dtype)
    if masked is None:
        mask = None     # the program without a mask operand
    elif masked:
        m = (rng.random((t, b)) > 0.3).astype(np.float32)
        m[0] = 1.0  # keep step 0 alive for all examples...
        m[:, -1] = 0.0  # ...but one: a fully masked row adds nothing to db
        mask = jnp.asarray(m, dtype)
    else:
        mask = jnp.ones((t, b), dtype)
    return x, Wx, bias, h0, c0, Wh, p, mask


def _loss_through(fn):
    def loss(x, Wx, bias, h0, c0, Wh, p, mask):
        y, hT, cT = (o.astype(jnp.float32)
                     for o in fn(x, Wx, bias, h0, c0, Wh, p, mask))
        w = jnp.cos(jnp.arange(y.size, dtype=y.dtype)).reshape(y.shape)
        return (jnp.sum(y * w) + 2.0 * jnp.sum(jnp.sin(hT))
                + 0.5 * jnp.sum(cT * cT))
    return loss


GRAD_NAMES = ["dx", "dWx", "db", "dh0", "dc0", "dWh", "dp"]
GRAD_ARGNUMS = tuple(range(len(GRAD_NAMES)))


def _assert_close(got, want, dtype, err_msg=""):
    """f32: elementwise. bf16: ``want`` is the f32 result on the same
    rounded inputs, and the worst entry is held to a share of the largest
    (rounding the result alone costs 0.4%; the worst seen is 0.55%)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4,
                                   err_msg=err_msg)
    else:
        assert np.all(np.isfinite(got)), err_msg
        scale = max(np.abs(want).max(), 1e-3)
        assert np.abs(got - want).max() / scale < 0.02, err_msg


def _lstm_net_loss(bidirectional=False, t=6, b=8, n=128, f=16):
    """(params, loss(params)) of a Graves LSTM + RnnOutput net on a batch."""
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers_recurrent import (
        GravesBidirectionalLSTM, GravesLSTM, RnnOutput)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    lstm = GravesBidirectionalLSTM if bidirectional else GravesLSTM
    conf = (NeuralNetConfiguration.builder().seed(3).list()
            .layer(lstm(n_out=n, activation="tanh"))
            .layer(RnnOutput(n_out=f, loss="mcxent", activation="softmax"))
            .set_input_type(InputType.recurrent(f)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x, y = (jnp.asarray(np.eye(f, dtype=np.float32)[rng.integers(0, f, (b, t))])
            for _ in range(2))

    def loss(params):
        return net._loss(params, net.state, x, y, None, None, None)[0]

    return net.params, loss


def _reduce_sums_over(jaxpr, shapes):
    """Every ``reduce_sum`` over an operand of one of ``shapes`` in
    ``jaxpr`` and the jaxprs its equations carry, the inside of a
    ``pallas_call`` excepted."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if (eqn.primitive.name == "reduce_sum"
                and tuple(eqn.invars[0].aval.shape) in shapes):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _reduce_sums_over(sub, shapes)
    return found


class TestLstmBackendEquivalence:
    """Interpret-mode pallas vs xla on CPU (runs everywhere)."""

    def setup_method(self):
        os.environ["DL4J_TPU_PALLAS_INTERPRET"] = "1"

    def teardown_method(self):
        os.environ.pop("DL4J_TPU_PALLAS_INTERPRET", None)

    def _pallas(self, *args):
        return lstm_ops._lstm_seq_pallas(*args)

    def _xla(self, *args):
        return lstm_ops.lstm_sequence_xla(*args)

    @pytest.mark.parametrize("masked", [False, True])
    def test_forward_equivalence(self, masked):
        args = _data(masked=masked)
        y_p, hT_p, cT_p = self._pallas(*args)
        y_x, hT_x, cT_x = self._xla(*args)
        np.testing.assert_allclose(y_p, y_x, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(hT_p, hT_x, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(cT_p, cT_x, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_gradient_equivalence(self, dtype, masked):
        # the CuDNNGradientChecks analogue: d/d{x, Wx, b, h0, c0, Wh, p} must
        # match between the hand-written backward kernel (which sums the
        # bias gradient itself) and autodiff of the scan path on
        # identical inputs
        args = _data(t=4, b=16, n=128, dtype=dtype, masked=masked)
        g_p = jax.grad(_loss_through(self._pallas), argnums=GRAD_ARGNUMS)(
            *args)
        # the scan in bf16 rounds every intermediate, so the kernel (f32
        # inside) is held to the scan in f32 on the same rounded inputs
        g_x = jax.grad(_loss_through(self._xla), argnums=GRAD_ARGNUMS)(
            *(a.astype(jnp.float32) for a in args))
        for name, gp, gx, a in zip(GRAD_NAMES, g_p, g_x, args):
            assert gp.shape == a.shape and gp.dtype == dtype, name
            _assert_close(gp, gx, dtype,
                          f"pallas/xla gradient mismatch for {name}")

    def _assert_matches_the_scan(self, args, dtype, label):
        """Outputs and all seven gradients of the kernels on ``args``
        against the scan in f32 on the same (rounded) inputs."""
        as_f32 = [None if a is None else a.astype(jnp.float32) for a in args]
        # a TPU's default f32 dot is one bf16 pass (the class also runs
        # there, under DL4J_TPU_TESTS=1), which these bounds do not allow
        with jax.default_matmul_precision("highest"):
            outs_p, outs_x = self._pallas(*args), self._xla(*as_f32)
            g_p = jax.grad(_loss_through(self._pallas),
                           argnums=GRAD_ARGNUMS)(*args)
            g_x = jax.grad(_loss_through(self._xla),
                           argnums=GRAD_ARGNUMS)(*as_f32)
        for name, out_p, out_x in zip(("y", "hT", "cT"), outs_p, outs_x):
            assert out_p.dtype == dtype, name
            if dtype == jnp.float32:
                np.testing.assert_allclose(out_p, out_x, rtol=1e-5,
                                           atol=1e-5, err_msg=name)
            else:
                _assert_close(out_p, out_x, dtype, name)
        for name, gp, gx, a in zip(GRAD_NAMES, g_p, g_x, args):
            assert gp.shape == a.shape and gp.dtype == dtype, name
            assert float(jnp.abs(gx).max()) > 0, name
            _assert_close(gp, gx, dtype, f"{label}: {name}")

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("t", [6, 1], ids=["t6", "t1"])
    @pytest.mark.parametrize("mask", ["none", "ragged"])
    def test_one_hidden_stream(self, mask, t, dtype):
        # the forward saves the carried hidden state alone: the backward
        # takes h_prev[t] from that stream one block back and from h0 at
        # t = 0 (the new edge: dh0 and the t = 0 term of dWh, which at
        # T = 1 is all of dWh), and without a mask neither kernel is
        # given one. Ragged: every length from 0 (masked from the first
        # step, so the row hands h0 through to hT) to t, and a full row
        # with one step taken out; h0 and c0 are non-zero throughout.
        args = list(_data(t=t, b=16, n=128, dtype=dtype, masked=None))
        if mask == "ragged":
            m = (np.arange(t)[:, None] < np.arange(16)[None, :] % (t + 1))
            m = m.astype(np.float32)
            assert not m[:, 0].any() and m[:, t].all() and m[:, 2 * t + 1].all()
            m[t // 2, 2 * t + 1] = 0.0
            args[-1] = jnp.asarray(m, dtype)
        self._assert_matches_the_scan(args, dtype, f"{mask} t={t}")

    def _calls_traced(self, fn, *args):
        """The labels of ``dl4j_lstm_kernel_calls_total`` that one trace
        of ``fn`` counts, each with its count."""
        from deeplearning4j_tpu.observability import metrics
        saved = metrics.set_registry(metrics.MetricsRegistry())
        try:
            jax.make_jaxpr(fn)(*args)
            fam = metrics.get_registry().snapshot().get(
                "dl4j_lstm_kernel_calls_total", [])
        finally:
            metrics.set_registry(saved)
        return [(s["labels"], s["value"]) for s in fam if s["value"]]

    def _time_blocks_traced(self, fn, *args):
        """{direction: Tb} of the kernel calls one trace of ``fn`` makes."""
        return {labels["direction"]: int(labels["time_block"])
                for labels, _ in self._calls_traced(fn, *args)}

    def _projections_traced(self, fn, *args):
        """{(direction, projection): calls} of one trace of ``fn``."""
        return {(labels["direction"], labels["projection"]): int(count)
                for labels, count in self._calls_traced(fn, *args)}

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("mask", ["none", "ragged"])
    @pytest.mark.parametrize("t,tb", [(16, 4), (12, 4), (6, 2), (7, 1),
                                      (1, 1)])
    def test_every_rung_of_the_time_block_ladder(self, t, tb, mask, dtype):
        # a grid step is tb timesteps: forward and all seven gradients
        # against the scan at every rung (16 is four blocks of 4, 12 is
        # three). Ragged: lengths 0 .. t, so rows end inside a block, at a
        # block's edge and in the block after (16 rows: every length of
        # t <= 15 is there, some twice), the longest row with a step taken
        # out; h0 and c0 are non-zero.
        args = list(_data(t=t, b=16, n=128, dtype=dtype, masked=None, seed=t))
        if mask == "ragged":
            m = (np.arange(t)[:, None] < np.arange(16)[None, :] % (t + 1))
            m = m.astype(np.float32)
            full = min(t, 15)       # the longest row: t steps, 15 of 16
            assert m[:, full].sum() == full
            m[t // 2, full] = 0.0
            args[-1] = jnp.asarray(m, dtype)
        grad = jax.grad(_loss_through(self._pallas), argnums=GRAD_ARGNUMS)
        assert self._time_blocks_traced(grad, *args) == {
            "forward": tb, "backward": tb}
        self._assert_matches_the_scan(args, dtype, f"{mask} t={t}")

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("mask", ["none", "ragged"])
    @pytest.mark.parametrize("n_in", [80, 128])
    @pytest.mark.parametrize("t,tb", [(8, 4), (6, 2), (3, 1)])
    def test_kernel_projects_a_narrow_input(self, t, tb, n_in, mask, dtype):
        # one-hot-wide rows (80, no whole lane tile of K) and rows as wide
        # as the hidden state (128, a stacked layer): the forward kernel
        # reads x, Wx and the bias and forms x Wx + h Wh + b itself,
        # at every rung; outputs and all seven gradients, dx and dWx among
        # them, against the scan
        args = list(_data(t=t, b=16, n=128, dtype=dtype, masked=None,
                          seed=t, n_in=n_in))
        if mask == "ragged":
            m = (np.arange(t)[:, None] < np.arange(16)[None, :] % (t + 1))
            args[-1] = jnp.asarray(m.astype(np.float32), dtype)
        grad = jax.grad(_loss_through(self._pallas), argnums=GRAD_ARGNUMS)
        # (one trace: a second make_jaxpr of the same function is cached)
        assert sorted(self._calls_traced(grad, *args), key=str) == [
            ({"direction": "backward", "time_block": str(tb),
              "projection": "outside"}, 1),
            ({"direction": "forward", "time_block": str(tb),
              "projection": "kernel"}, 1)]
        eqn, = (e for e in jax.make_jaxpr(self._pallas)(*args).jaxpr.eqns
                if e.primitive.name == "custom_vjp_call")
        # nothing [t, b, 4n] goes into the kernels: no xz exists
        assert (t, 16, 4 * 128) not in [v.aval.shape for v in eqn.invars]
        self._assert_matches_the_scan(args, dtype, f"n_in={n_in} {mask}")

    @pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask"])
    def test_wide_input_is_projected_outside(self, masked):
        # 136 columns are wider than the 128 of the hidden state: xz is
        # made by a matmul ahead of the forward kernel, which is given no Wx
        args = _data(t=4, b=16, n=128, masked=masked, n_in=136)
        grad = jax.grad(_loss_through(self._pallas), argnums=GRAD_ARGNUMS)
        assert self._projections_traced(grad, *args) == {
            ("forward", "outside"): 1, ("backward", "outside"): 1}
        fwd, = (e for e in jax.make_jaxpr(
            lambda *a: lstm_ops._lstm_seq_fwd(*a)[0])(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call")
        assert (4, 16, 4 * 128) in [v.aval.shape for v in fwd.invars]
        assert (136, 4 * 128) not in [v.aval.shape for v in fwd.invars]

    @pytest.mark.parametrize("n_in,n,want", [
        (1, 128, True), (80, 512, True), (128, 128, True), (200, 200, True),
        (512, 512, True), (512, 1024, True), (129, 128, False),
        (136, 128, False), (513, 512, False), (1024, 512, False)])
    def test_who_projects_is_a_function_of_the_shapes(self, n_in, n, want):
        # no wider than the hidden state: characters, an embedding, a
        # stacked layer of the same width; a wider input is left to the
        # matmul. And only while the kernel of one timestep a grid step,
        # Wx resident, asks for no more VMEM than the cap
        asked = []
        fits = lambda tb: asked.append(tb) or lstm_ops._VMEM_CAP
        assert lstm_ops._kernel_projects(n_in, n, fits) is want
        assert asked == [1] * (n_in <= n)
        assert not lstm_ops._kernel_projects(
            n_in, n, lambda tb: lstm_ops._VMEM_CAP + 1)

    @pytest.mark.parametrize("dtype,n,want", [
        (jnp.bfloat16, 512, "kernel"), (jnp.float32, 512, "kernel"),
        (jnp.bfloat16, 1024, "kernel"), (jnp.float32, 1536, "outside"),
        (jnp.float32, 2048, "outside")], ids=str)
    def test_weights_that_do_not_fit_vmem_are_projected_outside(
            self, dtype, n, want):
        # a stacked layer of its own width at b=256, traced only: Wx and
        # Wh are both whole in VMEM where the kernel projects, and in f32
        # at n = 1,536 Mosaic has no room for the two (compiled for a
        # described v5e: RESOURCE_EXHAUSTED, where the kernel given xz
        # compiles). The rule sees it in the request, and the call takes
        # the matmul ahead of the kernel
        sds = jax.ShapeDtypeStruct
        row = sds((256, n), dtype)
        args = (sds((8, 256, n), dtype), sds((n, 4 * n), dtype),
                sds((4 * n,), dtype), row, row, sds((n, 4 * n), dtype),
                sds((3, n), dtype), None)
        calls = self._calls_traced(
            lambda *a: lstm_ops._lstm_seq_fwd(*a)[0], *args)
        assert [labels["projection"] for labels, _ in calls] == [want]

    def test_counter_names_where_each_call_projects(self):
        # a stack: 80 columns into 128 cells, those 128 into 256, those
        # 256 into 128 (wider than the hidden state: a matmul ahead of the
        # kernel). One forward and one backward call a layer
        def stack(x, layers):
            for Wx, bias, h0, c0, Wh, p in layers:
                x = self._pallas(x, Wx, bias, h0, c0, Wh, p, None)[0]
            return jnp.sum(x.astype(jnp.float32))

        x = _data(t=4, b=16, n_in=80, masked=None)[0]
        layers = [_data(t=4, b=16, n=n, n_in=n_in, masked=None)[1:-1]
                  for n_in, n in ((80, 128), (128, 256), (256, 128))]
        assert self._projections_traced(jax.grad(stack), x, layers) == {
            ("forward", "kernel"): 2, ("forward", "outside"): 1,
            ("backward", "outside"): 3}

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("masked", [None, True], ids=["no_mask", "mask"])
    def test_xla_backend_is_the_scan_over_x_Wx_plus_b(self, masked, dtype):
        # what the layer wrote before the op owned the projection: one
        # einsum over [b, t, f], the bias added to its time-major result,
        # the scan of the cell. The xla backend is that to the bit, under
        # jit as the nets run it
        x, Wx, bias, h0, c0, Wh, p, mask = _data(
            t=7, b=8, n=128, dtype=dtype, masked=masked, n_in=80)

        def before(x_bt, Wx, bias, h0, c0, Wh, p, mask):
            xw = jnp.einsum("btf,fg->btg", x_bt, Wx)
            xz_t = jnp.moveaxis(xw, 1, 0) + bias
            step = lambda carry, inp: lstm_ops._cell_step(
                Wh, p, jax.nn.sigmoid, jnp.tanh, carry, inp)
            if mask is None:
                (hT, cT), ys = jax.lax.scan(
                    lambda carry, z: step(carry, (z, None)), (h0, c0), xz_t)
            else:
                (hT, cT), ys = jax.lax.scan(step, (h0, c0), (xz_t, mask))
            return ys, hT, cT

        want = jax.jit(before)(jnp.moveaxis(x, 0, 1), Wx, bias, h0, c0, Wh,
                               p, mask)
        got = jax.jit(lambda *a: self._xla(jnp.moveaxis(a[0], 1, 0), *a[1:]))(
            jnp.moveaxis(x, 0, 1), Wx, bias, h0, c0, Wh, p, mask)
        for name, g, w in zip(("y", "hT", "cT"), got, want):
            assert g.dtype == dtype, name
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32), name)

    @pytest.mark.parametrize("only", ["h0_at_t0", "row_across_blocks"])
    def test_dWh_term_that_crosses_a_block_edge(self, only):
        # T is two blocks of the longest rung. dWh is the sum over t of
        # h_prev[t]^T dz[t], and a masked step has dz = 0, so the mask
        # leaves one term standing. h0_at_t0: only t = 0 is kept, and the
        # term is h0^T dz[0], which the last grid step selects. row_across
        # _blocks: only the last timestep of the first block and the
        # first of the second (edge - 1 and edge) are kept and h0 is
        # zero, so edge - 1 adds nothing and the term is
        # hk[edge - 1]^T dz[edge]: a block's first timestep, whose h_prev
        # is the last row of the block before.
        edge = lstm_ops._TIME_BLOCKS[0]
        t, b, n = 2 * edge, 16, 128
        # x is xw and Wx the identity, so that dx is the kernel's dxz
        xw, _, bias, h0, c0, Wh, p, _ = _data(t=t, b=b, n=n, masked=None,
                                              n_in=4 * n)
        kept = [0] if only == "h0_at_t0" else [edge - 1, edge]
        if only == "row_across_blocks":
            h0 = jnp.zeros_like(h0)
        m = np.zeros((t, b), np.float32)
        m[kept] = 1.0
        args = (xw, jnp.eye(4 * n, dtype=xw.dtype), bias, h0, c0, Wh, p,
                jnp.asarray(m))
        assert edge > 1 and self._time_blocks_traced(
            self._pallas, *args) == {"forward": edge}
        with jax.default_matmul_precision("highest"):
            g_p = jax.grad(_loss_through(self._pallas),
                           argnums=GRAD_ARGNUMS)(*args)
            g_x = jax.grad(_loss_through(self._xla),
                           argnums=GRAD_ARGNUMS)(*args)
            hk = lstm_ops._lstm_seq_kernels(*args)[0]
        for name, gp, gx in zip(GRAD_NAMES, g_p, g_x):
            _assert_close(gp, gx, jnp.float32, f"{only}: {name}")
        dxw, dWh = g_p[0], g_p[5]
        assert not np.asarray(dxw)[[k for k in range(t) if k not in kept]].any()
        h_prev = h0 if only == "h0_at_t0" else hk[edge - 1]
        with jax.default_matmul_precision("highest"):
            term = h_prev.T @ dxw[kept[-1]]
        assert float(jnp.abs(term).max()) > 1e-2
        np.testing.assert_allclose(dWh, term, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("t,want", [(1, 1), (7, 1), (13, 1), (6, 2),
                                        (50, 2), (12, 4), (16, 4), (64, 4),
                                        (1000, 4), (1024, 4)])
    def test_time_block_is_a_function_of_the_length(self, t, want):
        # the longest of 4, 2, 1 that divides T, while the request
        # fits: T = 1 (rnn_time_step, decode) and a prime T give 1
        assert lstm_ops._time_block(t, lambda tb: 0) == want
        assert t % want == 0
        # a request over the cap sends the call down the ladder, to 1 at
        # the last whatever 1 asks
        over = lstm_ops._VMEM_CAP + 1
        assert lstm_ops._time_block(
            t, lambda tb: over if tb > 2 else 0) == min(want, 2)
        assert lstm_ops._time_block(t, lambda tb: over - 1) == want
        assert lstm_ops._time_block(t, lambda tb: over) == 1

    @pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask"])
    @pytest.mark.parametrize("t,tb", [(1024, 4), (1021, 1), (1, 1)])
    def test_blocking_of_the_projected_forward_at_the_bench_shape(
            self, t, tb, masked):
        # layer 0 of the char-RNN: b=256, n=512, 80 one-hot columns in
        # bf16, traced only. The streamed input is the (Tb, b, 80) block
        # of x; Wx [80, 4n] and the bias [1, 4n] are whole, at a constant
        # index, beside Wh; the results are those of the forward given xz
        b, n, n_in, cd = 256, 512, 80, jnp.bfloat16
        sds = jax.ShapeDtypeStruct
        row = sds((b, n), cd)
        args = (sds((t, b, n_in), cd), row, row, sds((n, 4 * n), cd),
                sds((3, n), cd), sds((t, b), cd) if masked else None,
                sds((n_in, 4 * n), cd), sds((4 * n,), cd))
        assert self._calls_traced(lstm_ops._fwd_call, *args) == [
            ({"direction": "forward", "time_block": str(tb),
              "projection": "kernel"}, 1)]
        eqn, = (e for e in jax.make_jaxpr(lstm_ops._fwd_call)(*args).jaxpr.eqns
                if e.primitive.name == "pallas_call")
        grid = eqn.params["grid_mapping"]
        assert grid.grid == (t // tb,)
        blocks = [tuple(getattr(d, "block_size", d) for d in m.block_shape)
                  for m in grid.block_mappings]
        assert [blk for blk in blocks if len(blk) == 3] == (
            [(tb, b, 1)] * masked
            + [(tb, b, w) for w in (n_in, n, 4 * n, n)])    # x; hk, G, c_prev
        assert blocks[masked + 1:masked + 3] == [(n_in, 4 * n), (1, 4 * n)]
        limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert lstm_ops._VMEM_DEFAULT <= limit <= lstm_ops._VMEM_CAP
        assert [(o.shape, o.dtype) for o in jax.eval_shape(lstm_ops._fwd_call, *args)] == [
            (s, cd) for s in ((t, b, n), (b, n), (b, n), (t, b, 4 * n),
                              (t, b, n))]      # hk, hT, cT, G, c_prev

    @pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask"])
    @pytest.mark.parametrize("t,tb", [(1024, 4), (1021, 1), (1, 1)])
    def test_blocking_of_the_calls_at_the_bench_shape(self, t, tb, masked):
        # b=256, n=512 in bf16, traced only. The request stays under the
        # cap with and without a mask; the grid is T / Tb long; every
        # [T, ...] operand and result moves in (Tb, b, width) blocks and
        # the backward is given one more (1, b, n) row of hk; at Tb = 1
        # grid and blocks are those of the kernel of one timestep a grid
        # step (no second hk operand).
        b, n, cd = 256, 512, jnp.bfloat16
        seq = lambda width: jax.ShapeDtypeStruct((t, b, width), cd)
        row = jax.ShapeDtypeStruct((b, n), cd)
        Wh, p = (jax.ShapeDtypeStruct(s, cd) for s in ((n, 4 * n), (3, n)))
        mask = jax.ShapeDtypeStruct((t, b), cd) if masked else None
        calls = {
            "forward": (lstm_ops._fwd_call, (seq(4 * n), row, row, Wh, p,
                                             mask)),
            "backward": (lstm_ops._bwd_call, (
                (seq(4 * n), seq(n), seq(n), row, mask, Wh, p),
                (seq(n), row, row)))}
        streamed = {"forward": [4 * n, n, 4 * n, n],        # xz, hk, G, c_prev
                    "backward": [4 * n] + [n] * (tb > 1) + [n, n, 4 * n]}
        for direction, (call, args) in calls.items():
            assert self._time_blocks_traced(call, *args) == {direction: tb}
            eqn, = (e for e in jax.make_jaxpr(call)(*args).jaxpr.eqns
                    if e.primitive.name == "pallas_call")
            grid = eqn.params["grid_mapping"]
            assert grid.grid == (t // tb,)
            blocks = [tuple(getattr(d, "block_size", d)
                            for d in m.block_shape)
                      for m in grid.block_mappings]
            in_time = [blk for blk in blocks if len(blk) == 3]
            want = [(tb, b, 1)] * masked + [(tb, b, w)
                                            for w in streamed[direction]]
            if direction == "backward":     # ... and the row one back
                want.insert(masked + 1 + (tb > 1), (1, b, n))
            assert in_time == want, (direction, in_time)
            limit = eqn.params["compiler_params"][
                "mosaic_tpu"].vmem_limit_bytes
            assert lstm_ops._VMEM_DEFAULT <= limit <= lstm_ops._VMEM_CAP
            if tb == 1:     # what the one-timestep kernel asked before
                assert limit < 40 * 2 ** 20

    @pytest.mark.parametrize("masked", [False, True])
    def test_bias_gradient_is_the_sum_of_dxw(self, masked):
        # what autodiff would have reduced out of dxw, the kernel emits
        # (Wx is the identity, so dx is dxw)
        args = list(_data(t=4, b=16, n=128, masked=masked, n_in=512))
        args[1] = jnp.eye(512, dtype=args[0].dtype)
        with jax.default_matmul_precision("highest"):
            dxw, db = jax.grad(_loss_through(self._pallas),
                               argnums=(0, 2))(*args)
        np.testing.assert_allclose(db, jnp.sum(dxw, axis=(0, 1)),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("masked", [False, True])
    def test_wrapper_falls_back_when_unsupported(self, masked):
        # unaligned hidden size -> the registered pallas backend must
        # delegate to xla (the cuDNN-absent fallback path), forward and
        # every gradient, the bias's among them
        x, Wx, bias, h0, c0, Wh, p, mask = _data(t=3, b=4, n=24, seed=1,
                                                 masked=masked, n_in=12)
        assert not lstm_ops._pallas_supported(x, h0, "sigmoid", "tanh")
        args = (x, Wx, bias, h0, c0, Wh, p, mask if masked else None)
        for out_w, out_x in zip(lstm_ops.lstm_sequence_pallas(*args),
                                lstm_ops.lstm_sequence_xla(*args)):
            np.testing.assert_allclose(out_w, out_x, rtol=1e-6)
        g_w = jax.grad(_loss_through(lstm_ops.lstm_sequence_pallas),
                       argnums=GRAD_ARGNUMS)(*args)
        g_x = jax.grad(_loss_through(lstm_ops.lstm_sequence_xla),
                       argnums=GRAD_ARGNUMS)(*args)
        for name, gw, gx in zip(GRAD_NAMES, g_w, g_x):
            np.testing.assert_allclose(gw, gx, rtol=1e-6, err_msg=name)

    def test_registry_prefers_pallas(self):
        from deeplearning4j_tpu.ops import registry
        assert set(registry.backends("lstm_sequence")) == {"pallas", "xla"}
        assert registry.get("lstm_sequence") is lstm_ops.lstm_sequence_pallas

    @pytest.mark.parametrize("bidirectional", [False, True],
                             ids=["GravesLSTM", "GravesBidirectionalLSTM"])
    def test_layer_gradients_match_across_backends(self, bidirectional):
        # through _lstm_scan: every parameter's gradient, each direction's
        # bias among them, is the same whichever backend runs the loop
        from deeplearning4j_tpu.ops import registry
        params, loss = _lstm_net_loss(bidirectional)
        grads = {}
        for backend in ("pallas", "xla"):
            with registry.use_backend(backend):
                grads[backend] = jax.grad(loss)(params)
        leaves_p, _ = jax.tree_util.tree_flatten_with_path(grads["pallas"])
        leaves_x = jax.tree_util.tree_leaves(grads["xla"])
        assert sum("'b'" in str(path) for path, _ in leaves_p) == (
            3 if bidirectional else 2)
        for (path, gp), gx in zip(leaves_p, leaves_x):
            assert float(jnp.abs(gx).max()) > 0, path
            np.testing.assert_allclose(gp, gx, rtol=2e-4, atol=2e-6,
                                       err_msg=str(path))

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_no_reduce_over_dxz_in_the_step(self, backend):
        # the bias gradient of a GravesLSTM is the kernel's: nothing in
        # the differentiated loss reads the [t, b, 4n] dxz again to sum
        # it. Under autodiff of the scan (the xla backend) that reduce is
        # how db is made, which also shows that the search finds one.
        from deeplearning4j_tpu.ops import registry
        t, b, n = 6, 8, 128
        params, loss = _lstm_net_loss(t=t, b=b, n=n)
        with registry.use_backend(backend):
            jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
        hits = _reduce_sums_over(jaxpr, {(t, b, 4 * n), (b, t, 4 * n)})
        assert ("pallas_call" in str(jaxpr)) == (backend == "pallas")
        if backend == "pallas":
            assert not hits, [str(e) for e in hits]
        else:
            assert hits


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
    """Same checks, compiled on hardware, bf16 — the dtype the bench runs.
    ``masked`` None is the program ``fit`` runs when it feeds no mask:
    kernels without a mask operand. 80 columns (Mosaic masks the K that
    is no whole lane tile) and 128 are projected by the forward kernel,
    136 by the matmul ahead of it."""

    @pytest.mark.parametrize("n_in", [80, 128, 136])
    @pytest.mark.parametrize("masked", [None, False])
    def test_forward_bf16(self, masked, n_in):
        args = _data(t=6, b=16, n=128, dtype=jnp.bfloat16, masked=masked,
                     n_in=n_in)
        y_p, hT_p, cT_p = jax.jit(lstm_ops._lstm_seq_pallas)(*args)
        y_x, hT_x, cT_x = jax.jit(lstm_ops.lstm_sequence_xla)(*args)
        np.testing.assert_allclose(
            np.asarray(y_p, np.float32), np.asarray(y_x, np.float32),
            rtol=0.05, atol=0.05)

    @pytest.mark.parametrize("n_in", [80, 128, 136])
    @pytest.mark.parametrize("masked", [None, True])
    def test_gradient_bf16_finite_and_close(self, masked, n_in):
        args = _data(t=4, b=16, n=128, dtype=jnp.bfloat16, masked=masked,
                     n_in=n_in)
        g_p = jax.jit(jax.grad(_loss_through(lstm_ops._lstm_seq_pallas),
                               argnums=GRAD_ARGNUMS))(*args)
        g_x = jax.jit(jax.grad(_loss_through(lstm_ops.lstm_sequence_xla),
                               argnums=GRAD_ARGNUMS))(*args)
        for name, gp, gx in zip(GRAD_NAMES, g_p, g_x):
            gp = np.asarray(gp, np.float32)
            gx = np.asarray(gx, np.float32)
            assert np.all(np.isfinite(gp)), name
            scale = max(np.abs(gx).max(), 1e-3)
            assert np.abs(gp - gx).max() / scale < 0.1, name
