"""The short-convolution / attention decoder of zoo.lfm2_moe on the net's
own path, against the plain reference the benchmark holds it to
(benchmark/reference/lfm2_moe.py), at small widths on the CPU with
seeded weights: the gated short convolution (both executors of
ops/shortconv.py against a loop over positions), causal grouped-query
attention with head norms and rotation at heads of 64, a leading dense
layer, sigmoid-routed gated experts without a shared one, and a head
tied to the embedding.

Tolerances: the float32 policy runs the same mathematics as the
reference in another order (attention by tiles, grouped products by
sorted chunks), so the two agree to float32 rounding of sums of tens to
hundreds of terms: 2e-5 relative to the largest entry compared. The
suite runs with x64 on; every array here is float32 by construction.
"""

import io
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from deeplearning4j_tpu import (
    MultiLayerNetwork, NeuralNetConfiguration, zoo)
from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers_decoder import RoutedExperts
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.observability import moe as obs_moe
from deeplearning4j_tpu.observability import opindex
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import shortconv
from tests.test_nemotron_h import _dense_causal
from tests.test_sdar_moe import _count

RTOL = 2e-5
VOCAB, L, D = 64, 32, 32
SMALL = dict(pattern="cacc", n_dense=1, n_experts=16, experts_held=4,
             first_expert=4, vocab_size=VOCAB, hidden=D, n_heads=4,
             n_kv_heads=2, head_dim=8, mlp_width=48, expert_width=24,
             experts_per_token=3, dtype=zoo.F32)
HOW = dict(top_k=3, first_expert=4, head_dim=8)
SCOPES = ("conv_op", "short_conv", "attn", "causal_attention", "dense_mlp",
          "route", "experts")


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= rtol * max(np.abs(b).max(), 1e-30), (
        np.abs(a - b).max(), np.abs(b).max())


def _make(seed=1, **more):
    return zoo.lfm2_moe(seed=seed, **{**SMALL, **more})


def _batch(seed=0, rows=2):
    ids = np.random.default_rng(seed).integers(0, VOCAB, (rows, L + 1),
                                               dtype=np.int32)
    return DataSet(ids[:, :-1], ids[:, 1:])


@pytest.fixture(scope="module")
def net():
    return _make(learning_rate=3e-3)


@pytest.fixture(scope="module")
def batch():
    return _batch()


# ------------------------------------------------------------------ the net
@pytest.mark.parametrize("pattern,n_dense", [
    ("cacc", 1), ("cacc", 0), ("acca", 2), ("ca", 2),
    (zoo.models.LFM2_MOE_PATTERN, 2)], ids=str)
def test_layers_below_n_dense_are_dense_and_the_rest_expert_layers(
        pattern, n_dense):
    from deeplearning4j_tpu.nn.conf import layers_decoder as conf
    small = {**SMALL, "pattern": pattern, "n_dense": n_dense}
    if len(pattern) > 4:
        # the published pattern: the configuration alone, no arrays
        small.update(hidden=8, head_dim=2, mlp_width=8, expert_width=8,
                     vocab_size=8)
    net = zoo.lfm2_moe(**small)
    want = {("c", True): conf.ShortConvDenseBlock,
            ("a", True): conf.CausalDenseBlock,
            ("c", False): conf.ShortConvMoeBlock,
            ("a", False): conf.CausalMoeBlock}
    blocks = net.conf.layers[1:-2]
    assert len(blocks) == len(pattern)
    for i, (kind, layer) in enumerate(zip(pattern, blocks)):
        assert type(layer) is want[kind, i < n_dense], (i, kind)
        assert ref.kind_of(net.params[f"layer_{i + 1}"]) == (
            ("conv" if kind == "c" else "attn") + "_"
            + ("dense" if i < n_dense else "experts"))
    assert isinstance(net.conf.layers[0], conf.TokenEmbedding)
    assert isinstance(net.conf.layers[-2], conf.RmsNorm)
    assert net.conf.layers[-1].tied_to == "layer_0"
    assert net.conf.layers[-1].causal is True
    assert [ref.kind_of(net.params[n]) for n in ("layer_0",
            f"layer_{len(pattern) + 1}")] == [None, None]


def test_the_published_pattern_is_the_configs():
    pattern = zoo.models.LFM2_MOE_PATTERN
    assert len(pattern) == 40 and pattern.count("a") == 10
    assert pattern[:6] == "ccaccc" and pattern[-2:] == "ac"
    with pytest.raises(ValueError, match="may hold 'c'"):
        _make(pattern="cxc")
    with pytest.raises(ValueError, match="dense layers among"):
        _make(n_dense=5)


def test_logits_match_the_reference(net, batch):
    want = jax.jit(lambda p, s, x: ref.logits(p, s, x, **HOW))(
        net.params, net.state, jnp.asarray(batch.features))
    got = net.output(batch.features)
    assert got.shape == (2, L, VOCAB) and got.dtype == jnp.float32
    _close(got, want)


def test_loss_matches_the_reference(net, batch):
    want = float(jax.jit(lambda p, s, x, y: ref.loss(p, s, x, y, **HOW))(
        net.params, net.state, jnp.asarray(batch.features),
        jnp.asarray(batch.labels)))
    assert abs(net.score(batch) - want) <= 1e-5 * want
    assert 3.5 < want < 5.0                      # about log(64) at init


@pytest.fixture(scope="module")
def gradients(net, batch):
    x, y = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    got = jax.jit(jax.grad(lambda p: net._loss(
        p, net.state, x, y, None, None, None)[0]))(net.params)
    want = jax.jit(jax.grad(lambda p: ref.loss(
        p, net.state, x, y, **HOW)))(net.params)
    return got, want


OPERATOR = ["op_ln_g", "W_in", "conv_w", "W_out"]
ATTENTION = ["attn_ln_g", "Wq", "Wk", "Wv", "Wo", "q_norm_g", "k_norm_g"]
EXPERTS = ["ln_g", "Wr", "Wg", "Wu", "Wd"]
GROUPS = [(f"layer_{i}", leaf) for i, leaves in {
    0: ["W"],
    1: OPERATOR + ["ln_g", "Wg", "Wu", "Wd"],
    2: ATTENTION + EXPERTS,
    3: OPERATOR + EXPERTS,
    5: ["g"]}.items() for leaf in leaves]


@pytest.mark.parametrize("layer,name", GROUPS,
                         ids=[f"{l}.{n}" for l, n in GROUPS])
def test_gradient_of_every_parameter_group(net, gradients, layer, name):
    got, want = gradients
    assert sorted(got[layer]) == sorted(net.params[layer])
    assert float(jnp.abs(want[layer][name]).max()) > 0
    # the gradient of a sum of hundreds of terms, each side in its order
    _close(got[layer][name], want[layer][name], rtol=1e-4)


def test_the_tied_leaf_holds_the_sum_of_the_gathers_and_the_heads(
        net, gradients, batch):
    """The embedding's matrix is gathered from and multiplied by: the
    stored leaf's gradient is both users', summed."""
    x, y = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    names = ref._names(net.params)

    def untied(emb, head):
        p = {**net.params, names[0]: {"W": emb}}

        def one(row):
            h = emb[row]
            for name in names[1:-1]:
                h, _, _ = ref.block(ref.kind_of(p[name]), p[name],
                                    net.state.get(name, {}), h, **HOW)
            return ref.rms_norm(h, p[names[-1]]["g"], 1e-5) @ head.T
        z = jnp.stack([one(row) for row in x])
        return jnp.mean(jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, y[..., None], -1)[..., 0])

    w = net.params["layer_0"]["W"]
    gather, head = jax.jit(jax.grad(untied, (0, 1)))(w, w)
    got = gradients[0]["layer_0"]["W"]
    for part in (gather, head):
        assert float(jnp.abs(part).max()) > 0.05 * float(jnp.abs(got).max())
    _close(got, gather + head, rtol=1e-4)
    # dropping a user would be seen
    for part in (gather, head):
        assert np.abs(np.asarray(got - part)).max() > 100 * 1e-4 * np.abs(
            np.asarray(got)).max()


def test_the_tied_leaf_is_stored_and_counted_once(net):
    out = net.layers[-1]
    assert out.shares == {"Emb": ("layer_0", "W")}
    assert out.name not in net.params and out.name not in net.opt_state
    assert sorted(net.opt_state) == sorted(net.params)
    operator = D + D * 3 * D + D * 3 + D * D
    attention = D + 2 * D * 32 + 2 * D * 16 + 2 * 8
    experts = D + D * 16 + 4 * 3 * D * 24
    assert net.num_params() == (
        VOCAB * D                                   # embedding and head
        + operator + D + 3 * D * 48                 # the dense layer
        + attention + experts + 2 * (operator + experts) + D)
    # an untied head keeps a matrix of its own
    from deeplearning4j_tpu.nn.conf.layers_decoder import TokenOutput
    assert TokenOutput(n_out=4).tied_to is None


def test_save_and_load_keep_one_copy_of_the_tied_leaf(net, batch, tmp_path):
    from deeplearning4j_tpu.utils.serialization import (
        restore_multi_layer_network, write_model)
    path = str(tmp_path / "lfm2.zip")
    write_model(net, path)
    with zipfile.ZipFile(path) as zf:
        saved = list(np.load(io.BytesIO(zf.read("coefficients.npz"))))
    assert not [k for k in saved if "Emb" in k]
    assert len([k for k in saved if k.endswith("['W']")]) == 1
    assert len(saved) == len(jax.tree_util.tree_leaves(net.params))
    again = restore_multi_layer_network(path)
    np.testing.assert_array_equal(again.output(batch.features),
                                  net.output(batch.features))
    assert again.score(batch) == net.score(batch)
    assert again.num_params() == net.num_params()


def test_a_head_tied_to_another_shape_is_refused():
    from deeplearning4j_tpu.nn.conf.layers_decoder import (
        RmsNorm, TokenEmbedding, TokenOutput)
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3))
            .dtype(zoo.F32).list()
            .layer(TokenEmbedding(n_out=8)).layer(RmsNorm())
            .layer(TokenOutput(n_out=12, causal=True, tied_to="layer_0"))
            .set_input_type(InputType.recurrent(16)).build())
    with pytest.raises(ValueError, match="need its transpose"):
        MultiLayerNetwork(conf).init().output(np.zeros((1, 4), np.int32))


# --------------------------------------------------- the short convolution
def _loop(bcx, w):
    """``C * conv(B * x~)`` one position and one tap at a time."""
    bcx, w = np.asarray(bcx, np.float64), np.asarray(w, np.float64)
    d, k = w.shape
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    g = b * x
    y = np.zeros(b.shape)
    for t in range(bcx.shape[1]):
        for tap in range(k):
            at = t - (k - 1) + tap
            if at >= 0:
                y[:, t] += w[:, tap] * g[:, at]
    return c * y


def _conv_inputs(rows, length, d, k, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (rows, length, 3 * d), jnp.float32),
            jax.random.normal(keys[1], (d, k), jnp.float32),
            jax.random.normal(keys[2], (rows, length, d), jnp.float32))


def _loop_grads(bcx, w, dy):
    """The backward as the op's docstring writes it, position by
    position."""
    bcx, w, dy = (np.asarray(a, np.float64) for a in (bcx, w, dy))
    d, k = w.shape
    length = bcx.shape[1]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    g = b * x
    s = _loop(np.concatenate([b, np.ones_like(c), x], -1), w)
    ds = dy * c
    dg, dw = np.zeros(g.shape), np.zeros(w.shape)
    for t in range(length):
        for tap in range(k):
            at = t - (k - 1) + tap
            if at >= 0:
                dg[:, at] += w[:, tap] * ds[:, t]
                dw[:, tap] += np.sum(ds[:, t] * g[:, at], axis=0)
    return np.concatenate([dg * x, dy * s, dg * b], -1), dw


@pytest.mark.parametrize("backend,rows,length,d,k", [
    ("xla", 2, 21, 16, 3),
    # three time tiles of 16: a halo before and after the middle one
    ("pallas", 2, 48, 128, 3),
    # one tile of 64, two slabs of columns, a filter of 4
    ("pallas", 1, 64, 256, 4),
    # 40 positions are no whole blocks of 16: refused, the formula runs
    ("xla", 1, 40, 128, 3)],
    ids=["xla-21", "pallas-48-3tiles", "pallas-64-k4", "refused-40"])
def test_gated_short_conv_is_the_loop_over_positions(monkeypatch, backend,
                                                     rows, length, d, k):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    bcx, w, dy = _conv_inputs(rows, length, d, k)
    assert shortconv.short_conv_supported(bcx, w) == (backend == "pallas")
    before = {b: _count("dl4j_short_conv_calls_total", backend=b)
              for b in ("xla", "pallas")}
    y, (dbcx, dw) = jax.jit(jax.value_and_grad(
        lambda a, b: jnp.sum(shortconv.gated_short_conv(a, b) * dy),
        (0, 1)))(bcx, w)
    other = "xla" if backend == "pallas" else "pallas"
    assert _count("dl4j_short_conv_calls_total",
                  backend=backend) > before[backend]
    assert _count("dl4j_short_conv_calls_total",
                  backend=other) == before[other]
    out = shortconv.gated_short_conv(bcx, w)
    assert out.shape == (rows, length, d) and out.dtype == bcx.dtype
    _close(out, _loop(bcx, w))
    want_dbcx, want_dw = _loop_grads(bcx, w, dy)
    _close(dbcx, want_dbcx)
    _close(dw, want_dw, rtol=1e-4)
    assert abs(float(y) - float(np.sum(_loop(bcx, w) * np.asarray(dy)))
               ) <= 1e-4 * abs(float(y))


@pytest.mark.parametrize("interpret", ["0", "1"], ids=["xla", "pallas"])
def test_the_first_rows_see_zeros_and_batch_rows_do_not_leak(monkeypatch,
                                                             interpret):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", interpret)
    bcx, w, _ = _conv_inputs(2, 32, 128, 3, seed=1)
    d = 128
    y = np.asarray(shortconv.gated_short_conv(bcx, w))
    b, c, x = (np.asarray(bcx[..., i * d:(i + 1) * d]) for i in range(3))
    g, wn = b * x, np.asarray(w)
    # row 0 has the last tap alone, row 1 the last two
    _close(y[:, 0], c[:, 0] * wn[:, 2] * g[:, 0])
    _close(y[:, 1], c[:, 1] * (wn[:, 2] * g[:, 1] + wn[:, 1] * g[:, 0]))
    # the end of batch row 0 is not the past of batch row 1
    alone = np.asarray(shortconv.gated_short_conv(bcx[1:], w))
    np.testing.assert_array_equal(alone[0], y[1])
    # and a later position moves no earlier one
    later = bcx.at[:, 20:].set(0.0)
    np.testing.assert_array_equal(
        np.asarray(shortconv.gated_short_conv(later, w))[:, :20], y[:, :20])


def test_a_projection_of_another_width_is_refused():
    with pytest.raises(ValueError, match=r"\[b, L, 3 d\]"):
        shortconv.gated_short_conv(jnp.zeros((1, 8, 24)), jnp.zeros((9, 3)))


def test_the_operator_is_the_references(net):
    layer, p = net.layers[1], net.params["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (L, D), jnp.float32)
    _close(layer._operator(p, x[None])[0], ref.short_conv(p, x))
    # B, C, x~ in this order: another order is another operator
    swapped = {**p, "W_in": jnp.concatenate(
        [p["W_in"][:, D:2 * D], p["W_in"][:, :D], p["W_in"][:, 2 * D:]], 1)}
    added = ref.short_conv(p, x) - x
    assert float(jnp.abs(ref.short_conv(swapped, x) - x - added).max()
                 ) > 0.1 * float(jnp.abs(added).max())


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("rows,dh,heads,kv_heads,kernels", [
    # the cell's heads: 64 columns, four query heads a key/value head
    (512, 64, 8, 2, True), (256, 64, 4, 4, True),
    # the other decoders' heads still take the kernels
    (256, 128, 4, 2, True), (512, 256, 2, 2, True),
    (256, 32, 4, 2, False), (256, 96, 4, 2, False),
    # two key tiles of 512: a query tile of 256 meets both, so the fused
    # backward gathers dQ across steps at heads of 64
    (1024, 64, 8, 2, True)],
    ids=["64-4on1", "64-1on1", "128", "256", "32-refused", "96-refused",
         "64-4on1-1024"])
def test_causal_attention_at_heads_of_64_is_a_dense_masked_softmax(
        monkeypatch, rows, dh, heads, kv_heads, kernels):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(k[0], (1, rows, heads, dh), jnp.float32)
    kk, v = (jax.random.normal(k[i], (1, rows, kv_heads, dh), jnp.float32)
             for i in (1, 2))
    g = jax.random.normal(k[3], q.shape, jnp.float32)
    assert att.causal_attention_supported(q, kk, v) is kernels
    if not kernels:
        return
    before = _count("dl4j_causal_attention_calls_total", backend="pallas")

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * g),
                                          (0, 1, 2)))(q, kk, v)

    got = value_and_grads(att.causal_attention)
    want = value_and_grads(_dense_causal)
    assert _count("dl4j_causal_attention_calls_total",
                  backend="pallas") > before
    assert abs(got[0] - want[0]) <= 1e-4 * abs(want[0])
    for a, b in zip(got[1], want[1]):
        _close(a, b, rtol=1e-4)


def test_the_attention_operator_is_the_references(net):
    layer, p = net.layers[2], net.params["layer_2"]
    x = jax.random.normal(jax.random.PRNGKey(3), (L, D), jnp.float32)
    want = ref.attention(p, x, head_dim=8)
    _close(layer._causal_attention(p, x[None])[0], want)
    # written out: head norms, rotation, a dense masked softmax
    u = ref.rms_norm(x, p["attn_ln_g"], 1e-5)
    q = ref.rope(ref.rms_norm((u @ p["Wq"]).reshape(L, 4, 8),
                              p["q_norm_g"], 1e-5), 1e6)
    k = ref.rope(ref.rms_norm((u @ p["Wk"]).reshape(L, 2, 8),
                              p["k_norm_g"], 1e-5), 1e6)
    v = (u @ p["Wv"]).reshape(L, 2, 8)
    o = _dense_causal(q[None], k[None], v[None])[0]
    _close(x + o.reshape(L, -1) @ p["Wo"], want)


def test_the_rotation_pairs_column_i_with_i_plus_half_the_head():
    from deeplearning4j_tpu.nn.layers.decoder import _rotate
    x = jax.random.normal(jax.random.PRNGKey(5), (1, L, 2, 64), jnp.float32)
    pos = jnp.arange(L, dtype=jnp.int32)
    turned = _rotate(x, 1e6, pos)
    _close(turned[0], ref.rope(x[0], 1e6))
    np.testing.assert_array_equal(turned[:, 0], x[:, 0])       # position 0
    for i in (0, 5, 31):
        # the pair (i, i + 32) keeps its length and turns by p theta^(-i/32)
        _close(turned[..., i] ** 2 + turned[..., i + 32] ** 2,
               x[..., i] ** 2 + x[..., i + 32] ** 2, rtol=1e-4)
        angle = np.arange(L) * 1e6 ** (-i / 32)
        want = (np.asarray(x[0, :, 0, i]) * np.cos(angle)
                - np.asarray(x[0, :, 0, i + 32]) * np.sin(angle))
        _close(turned[0, :, 0, i], want, rtol=1e-4)
    # and the program turns with the same base
    other = _make(pattern="a", n_dense=0, rope_theta=10.0)
    _close(other.feed_forward(np.arange(L, dtype=np.int32)[None])[1][0],
           ref.block("attn_experts", other.params["layer_1"],
                     other.state["layer_1"],
                     other.params["layer_0"]["W"][:L],
                     **{**HOW, "theta": 10.0})[0])


# -------------------------------------------------------------- the experts
def _expert_net(held, first, d=32, experts=16, router_eps=1e-6):
    conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
            .dtype(zoo.F32)
            .weight_init({"type": "normal", "mean": 0.0, "std": 0.3})
            .list()
            .layer(RoutedExperts(
                n_out=d, n_experts=experts, experts_per_token=3,
                expert_width=24, experts_held=held, first_expert=first,
                eps=1e-5, router="sigmoid", routed_scale=1.0,
                router_eps=router_eps, expert_form="gated_silu"))
            .set_input_type(InputType.recurrent(d)).build())
    return MultiLayerNetwork(conf).init()


def _share(whole, held, first):
    """The net holding ``held`` experts from ``first`` on, with the
    weights ``whole`` (a net holding all of them) has for them."""
    part = _expert_net(held, first)
    p = dict(whole.params["layer_0"])
    for name in ("Wg", "Wu", "Wd"):
        p[name] = p[name][first:first + held]
    part.params = {**part.params, "layer_0": p}
    return part


def test_the_8_shares_add_up_to_the_uncut_layer():
    """What the 8 chips of a deployment add to a row, each its own two
    experts of 16 (there is no shared expert to count once), sums to
    what the uncut reference layer adds."""
    whole = _expert_net(16, 0)
    a = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 32), jnp.float32)
    p, s = whole.params["layer_0"], whole.state["layer_0"]
    assert sorted(p) == sorted(EXPERTS)
    uncut = jax.jit(lambda a: ref.experts(p, s, a, top_k=3)[0])(a[0]) - a[0]
    added = sum(
        np.asarray(_share(whole, 2, first).feed_forward(a)[0][0]) - a[0]
        for first in range(0, 16, 2))
    _close(added, uncut)
    # and one share is what the reference gives for that share
    part = _share(whole, 2, 6)
    want = jax.jit(lambda a: ref.experts(
        part.params["layer_0"], s, a, top_k=3, first_expert=6)[0])(a[0])
    _close(part.feed_forward(a)[0][0], want)


def test_the_bias_moves_the_choice_and_not_the_weights():
    whole = _expert_net(16, 0)
    layer, p = whole.layers[0], whole.params["layer_0"]
    logits = jax.random.normal(jax.random.PRNGKey(4), (40, 16))
    score = np.asarray(jax.nn.sigmoid(logits))
    plain, coef = layer._choose(logits, {"router_bias": jnp.zeros(16)})
    np.testing.assert_allclose(np.asarray(coef).sum(-1), 1.0, rtol=1e-5)
    bias = jnp.zeros(16).at[11].set(5.0)        # expert 11 always chosen
    moved, coef_b = layer._choose(logits, {"router_bias": bias})
    assert np.all((np.asarray(moved) == 11).any(-1))
    assert not np.all((np.asarray(plain) == 11).any(-1))
    # the weights come from the score without the bias
    picked = np.take_along_axis(score, np.asarray(moved), -1)
    _close(coef_b, picked / (picked.sum(-1, keepdims=True) + 1e-6))
    # through the layer, as the reference has it
    a = jax.random.normal(jax.random.PRNGKey(2), (24, 32))
    state = {**whole.state["layer_0"], "router_bias": bias}
    want = jax.jit(lambda a: ref.experts(p, state, a, top_k=3)[0])(a)
    whole.state = {"layer_0": state}
    _close(whole.feed_forward(a[None])[0][0], want)
    # no gradient reaches the bias
    grads = jax.jit(jax.grad(lambda s: jnp.sum(layer.apply(
        p, {**state, "router_bias": s}, a[None])[0])))(bias)
    assert not np.asarray(grads).any()


def test_the_epsilon_reaches_the_denominator():
    logits = jax.random.normal(jax.random.PRNGKey(4), (40, 16))
    state = {"router_bias": jnp.zeros(16)}
    sums = {}
    for eps in (1e-20, 1e-6, 0.5):
        layer = _expert_net(16, 0, router_eps=eps).layers[0]
        chosen, coef = layer._choose(logits, state)
        top = np.take_along_axis(np.asarray(jax.nn.sigmoid(logits)),
                                 np.asarray(chosen), -1).sum(-1)
        _close(np.asarray(coef).sum(-1), top / (top + eps))
        sums[eps] = np.asarray(coef).sum(-1)
    assert np.all(sums[0.5] < 0.9) and np.all(sums[1e-6] > 0.99999)
    # the default is what the other three decoders' routers have
    assert RoutedExperts().router_eps == 1e-20
    assert _make().conf.layers[2].router_eps == 1e-6
    # and the reference's takes it too
    a = jax.random.normal(jax.random.PRNGKey(2), (24, 32))
    net = _expert_net(16, 0, router_eps=0.5)
    want = ref.experts(net.params["layer_0"], net.state["layer_0"], a,
                       top_k=3, router_eps=0.5)[0]
    _close(net.feed_forward(a[None])[0][0], want)


# ------------------------------------------------------------ the net path
def test_fit_scan_of_8_equals_eight_single_steps_and_lowers_the_loss():
    ring = [_batch(seed) for seed in range(8)]
    one, eight = _make(2, learning_rate=1e-3), _make(2, learning_rate=1e-3)
    before = [one.score(ds) for ds in ring]
    for ds in ring:
        one.fit_batch(ds)
    eight.fit(ListDataSetIterator(ring), multi_step=8, device_prefetch=True)
    assert eight.iteration == one.iteration == 8
    for a, b in zip(jax.tree_util.tree_leaves(one.params),
                    jax.tree_util.tree_leaves(eight.params)):
        _close(b, a, rtol=1e-6)
    for name in ("layer_2", "layer_4"):
        np.testing.assert_array_equal(
            one.state[name]["expert_rows_total"],
            eight.state[name]["expert_rows_total"])
    assert abs(float(one.score_value) - float(eight.score_value)) < 1e-5
    assert all(eight.score(ds) < b for ds, b in zip(ring, before))


def test_fit_with_default_arguments_lowers_the_loss(net):
    ring = [_batch(seed) for seed in (4, 5)]
    assert ring[0].features.dtype == ring[0].labels.dtype == np.int32
    before = [net.score(ds) for ds in ring]
    net.fit(ListDataSetIterator(ring * 4))
    assert all(net.score(ds) < b for ds, b in zip(ring, before))
    assert net.output(ring[0].features).shape == (2, L, VOCAB)
    with pytest.raises(TypeError, match="integer ids"):
        net.output(ring[0].features.astype(np.float32))


def test_streaming_raises_with_a_sentence(net, batch):
    with pytest.raises(NotImplementedError, match="no streaming path"):
        net.rnn_time_step(batch.features[:, :8])


def test_configuration_round_trips_through_json(net):
    from deeplearning4j_tpu.nn.conf.core import MultiLayerConfiguration
    again = MultiLayerConfiguration.from_json(net.conf.to_json())
    assert again.layers == net.conf.layers
    assert again.layers[-1].tied_to == "layer_0"
    assert again.layers[2].router_eps == 1e-6
    assert again.layers[1].conv_kernel == 3


def test_every_op_of_the_step_is_placed_under_a_scope(net, batch):
    step = jax.jit(net._step_fn())
    args = net._step_args(net._batch_args(batch), jax.random.PRNGKey(0))
    index = opindex.parse(step.lower(*args).compile().as_text())
    seen = set()
    for entry in index.values():
        phase, _, _ = opindex.place(entry)
        if entry["opcode"] in ("fusion", "custom-call", "dot", "scatter",
                               "gather", "sort", "while"):
            assert phase != "unplaced", entry
        seen.add((phase, opindex.place(entry, scopes=SCOPES)[1]))
    for scope in SCOPES:
        assert ("forward", scope) in seen and ("backward", scope) in seen


def test_trace_time_counters_and_the_collector(batch):
    layers = _count("dl4j_short_conv_layers_traced_total")
    conv = _count("dl4j_short_conv_calls_total", direction="forward")
    attention = _count("dl4j_causal_attention_calls_total",
                       direction="forward")
    fresh = _make(3)
    fresh.fit(ListDataSetIterator([batch]))
    # three operator layers and one attention layer, traced by the step
    assert _count("dl4j_short_conv_layers_traced_total") >= layers + 3
    assert _count("dl4j_short_conv_calls_total",
                  direction="forward") >= conv + 3
    assert _count("dl4j_causal_attention_calls_total",
                  direction="forward") >= attention + 1
    counted = obs_moe.expert_rows(fresh)
    assert sorted(counted) == ["layer_2", "layer_3", "layer_4"]
    last, total = counted["layer_4"]
    assert last.shape == (4,) and int(total.sum()) == int(last.sum())
    # three experts a row, four of sixteen held: about 2 L * 3 / 4 pairs
    assert 0 < int(last.sum()) < 2 * L * 3
