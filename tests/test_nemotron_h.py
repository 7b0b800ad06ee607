"""The hybrid decoder of zoo.nemotron_h on the net's own path, against
the plain reference the benchmark holds it to
(benchmark/reference/nemotron_h.py), at small widths on the CPU with
seeded weights: Mamba-2 mixers, causal attention and sigmoid-routed
relu2 experts beside a shared expert.

Tolerances: the float32 policy runs the same mathematics as the
reference in another order (the recurrence by chunks, attention by
tiles, grouped products by sorted chunks), so the two agree to float32
rounding of sums of tens to hundreds of terms: 2e-5 relative to the
largest entry compared. The suite runs with x64 on; every array here is
float32 by construction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from benchmark.reference import sdar_moe as sdar_ref
from deeplearning4j_tpu import (
    MultiLayerNetwork, NeuralNetConfiguration, zoo)
from deeplearning4j_tpu.datasets import (
    BlockDiffusionPreProcessor, DataSet, ListDataSetIterator)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers_decoder import (
    RoutedExperts, TokenOutput)
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.observability import moe as obs_moe
from deeplearning4j_tpu.observability import opindex
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import grouped
from deeplearning4j_tpu.ops import ssm
from tests.test_sdar_moe import KERNEL_CASES, _count, _pairs

RTOL = 2e-5
VOCAB, L = 64, 32
SMALL = dict(pattern="MEM*E", n_experts=16, experts_held=4, first_expert=4,
             vocab_size=VOCAB, hidden=32, mamba_heads=4, mamba_head_dim=8,
             n_groups=2, state_size=16, chunk=8, n_heads=4, n_kv_heads=2,
             head_dim=16, expert_width=24, shared_width=40,
             experts_per_token=3, dtype=zoo.F32)
HOW = dict(top_k=3, first_expert=4, scale=2.5, groups=2, head_dim=16)
SCOPES = ("mamba", "ssm_conv", "ssm_scan", "ssm_norm", "attn",
          "causal_attention", "route", "experts", "shared_expert")


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= rtol * max(np.abs(b).max(), 1e-30), (
        np.abs(a - b).max(), np.abs(b).max())


def _make(seed=1, **more):
    return zoo.nemotron_h(seed=seed, **{**SMALL, **more})


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


# ------------------------------------------------ system against reference
def test_the_net_is_built_from_the_pattern(net):
    kinds = [type(layer.conf).__name__ for layer in net.layers]
    assert kinds == ["TokenEmbedding", "Mamba2Mixer", "RoutedExperts",
                     "Mamba2Mixer", "CausalAttention", "RoutedExperts",
                     "RmsNorm", "TokenOutput"]
    assert [ref.kind_of(net.params[f"layer_{i}"]) for i in range(1, 6)] == \
        list("MEM*E")
    with pytest.raises(ValueError, match="may hold 'M'"):
        _make(pattern="M-E")
    # the published pattern: 23 Mamba-2, 23 expert and 6 attention layers
    assert [zoo.models.NEMOTRON_H_PATTERN.count(k) for k in "ME*"] == [
        23, 23, 6]
    assert zoo.models.NEMOTRON_H_PATTERN.startswith("MEMEM*EME")


def test_logits_match_the_reference(net, batch):
    want = jax.jit(lambda p: ref.logits(p, net.state, batch.features,
                                        **HOW))(net.params)
    got = net.output(batch.features)
    assert got.shape == (2, L, VOCAB)
    _close(got, want)


def test_loss_matches_the_reference(net, batch):
    want = jax.jit(lambda p: ref.loss(p, net.state, batch.features,
                                      batch.labels, **HOW))(net.params)
    assert abs(net.score(batch) - float(want)) <= RTOL * float(want)


@pytest.fixture(scope="module")
def gradients(net, batch):
    x, y = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    got = jax.jit(jax.grad(lambda p: net._loss(
        p, net.state, x, y, None, None, None)[0]))(net.params)
    want = jax.jit(jax.grad(lambda p: ref.loss(p, net.state, x, y, **HOW)))(
        net.params)
    return got, want


GROUPS = [(f"layer_{i}", leaf) for i, leaves in {
    0: ["W"], 7: ["W"], 6: ["g"],
    1: ["ln_g", "W_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
        "norm_g", "W_out"],
    4: ["attn_ln_g", "Wq", "Wk", "Wv", "Wo"],
    5: ["ln_g", "Wr", "Wu", "Wd", "Ws_u", "Ws_d"]}.items()
    for leaf in leaves]


@pytest.mark.parametrize("layer,name", GROUPS,
                         ids=[f"{l}.{n}" for l, n in GROUPS])
def test_gradient_of_every_parameter_group(gradients, layer, name):
    got, want = gradients
    assert float(jnp.abs(want[layer][name]).max()) > 0
    # the gradient of a sum of hundreds of terms, each side in its order
    _close(got[layer][name], want[layer][name], rtol=1e-4)


# ---------------------------------------------------------- the recurrence
def _scan_args(length, seed=0, dt_scale=1.0, sizes=(2, 4, 8, 2, 16),
               dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    bs, heads, p, groups, n = sizes
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.float32)
    return (normal(k[0], (bs, length, heads, p)).astype(dtype),
            jax.nn.softplus(normal(k[1], (bs, length, heads))) * dt_scale,
            -jnp.exp(normal(k[2], (heads,))),
            normal(k[3], (bs, length, groups, n)).astype(dtype),
            normal(k[4], (bs, length, groups, n)).astype(dtype),
            normal(k[5], (heads,)))


def _one_position_at_a_time(x, dt, a, b, c, d):
    r = x.shape[2] // b.shape[2]
    return jnp.stack([ref.recurrence(
        x[i], dt[i], a, jnp.repeat(b[i], r, axis=1),
        jnp.repeat(c[i], r, axis=1), d) for i in range(x.shape[0])])


def _value_and_grads(fn, args, seed=7):
    g = jax.random.normal(jax.random.PRNGKey(seed), args[0].shape,
                          jnp.float32)
    return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * g),
                                      tuple(range(6))))(*args)


# the smallest shapes the kernels take: a pair of heads of 64 a group, two
# groups, a state of one lane tile, chunks of 128
KERNEL_SIZES = (1, 4, 64, 2, 128)
EXECUTORS = {"xla": (8, (2, 4, 8, 2, 16)), "pallas": (128, KERNEL_SIZES)}


@pytest.mark.parametrize("executor", sorted(EXECUTORS))
@pytest.mark.parametrize("chunks", [1, 2, 5])
@pytest.mark.parametrize("dt_scale", [1.0, 40.0], ids=["dt_1", "dt_40"])
def test_the_chunked_recurrence_is_the_sequential_one(monkeypatch, executor,
                                                      chunks, dt_scale):
    """Forward and the gradients in x, dt, A, B, C (and D), by the xla
    executor at toy sizes and by the kernels (interpreted) at the
    smallest shapes they take (``dt`` scaled so that a chunk of 128
    decays as the chunk of 8 does). At ``dt`` of 40 a chunk's decay
    ``exp(sum dt A)`` underflows to 0 in float32: the decay matrix is
    made of masked differences, so it holds zeros and the gradients stay
    finite."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    chunk, sizes = EXECUTORS[executor]
    args = _scan_args(chunk * chunks, dt_scale=dt_scale * 8 / chunk,
                      sizes=sizes)
    assert ssm.ssm_scan_supported(*args, chunk) == (executor == "pallas")
    if dt_scale > 1:
        assert float(jnp.exp(jnp.sum(args[1][:, :chunk] * args[2],
                                     axis=1)).min()) == 0.0
    before = {d: _count("dl4j_ssm_scan_calls_total", direction=d,
                        backend=executor) for d in ("forward", "backward")}
    got = _value_and_grads(lambda *a: ssm.ssm_scan(*a, chunk=chunk), args)
    want = _value_and_grads(_one_position_at_a_time, args)
    for direction, was in before.items():
        assert _count("dl4j_ssm_scan_calls_total", direction=direction,
                      backend=executor) == was + 1
    _close(got[0], want[0], rtol=1e-4)
    for a, b in zip(got[1], want[1]):
        assert np.all(np.isfinite(np.asarray(a)))
        _close(a, b, rtol=2e-4)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sizes,dt_scale", [
    ((2, 4, 64, 2, 128), 0.05),      # a pair of heads a lane tile
    ((2, 4, 64, 2, 128), 2.5),       # a chunk's decay underflows to zeros
    ((1, 4, 128, 2, 256), 0.05),     # a head a lane tile, a state of two
], ids=["pair", "pair-dt_large", "head_128"])
def test_the_kernels_are_the_chunked_form(monkeypatch, dtype, rtol, sizes,
                                          dt_scale):
    """The two kernels against ``_chunked`` and its autodiff, all six
    gradients, over two groups and three chunks (the carried state and
    its gradient cross two boundaries). With float32 operands the two
    order the same sums differently; with bfloat16 the chunked form's
    autodiff rounds the gradient of a rounded operand to bfloat16 and
    the kernel keeps it float32, so they agree to bfloat16's step."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    args = _scan_args(3 * 128, dt_scale=dt_scale, sizes=sizes, dtype=dtype)
    assert ssm.ssm_scan_supported(*args, 128)
    if dt_scale > 1:
        assert float(jnp.exp(jnp.sum(args[1][:, :128] * args[2],
                                     axis=1)).min()) == 0.0
    got = _value_and_grads(lambda *a: ssm.ssm_scan(*a, chunk=128), args)
    want = _value_and_grads(lambda *a: ssm._chunked(*a, 128), args)
    _close(got[0], want[0], rtol=rtol)
    for a, b, like in zip(got[1], want[1], args):
        assert a.dtype == like.dtype and a.shape == like.shape
        assert np.all(np.isfinite(np.asarray(a, np.float32)))
        _close(a, b, rtol=rtol)


REFUSALS = {
    "no_tpu_no_interpreter": dict(interpret="0"),
    "chunk_of_64": dict(chunk=64),
    "odd_heads_a_group": dict(sizes=(1, 6, 64, 2, 128)),
    "head_width_32": dict(sizes=(1, 8, 32, 2, 128)),
    "state_of_64": dict(sizes=(1, 4, 64, 2, 64)),
    "a_group_of_2048_lanes": dict(sizes=(1, 32, 64, 1, 128)),
    "float32_B_beside_bfloat16_x": dict(mixed=True),
}


@pytest.mark.parametrize("why", sorted(REFUSALS))
def test_what_the_kernels_refuse_runs_on_xla(monkeypatch, why):
    """Each refusal of ``ssm_scan_supported`` lands on the xla executor,
    silently, and the counter says so."""
    case = dict(interpret="1", chunk=128, sizes=KERNEL_SIZES, mixed=False)
    case.update(REFUSALS[why])
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", case["interpret"])
    args = list(_scan_args(128, sizes=case["sizes"]))
    if case["mixed"]:
        args[0] = args[0].astype(jnp.bfloat16)
    chunk = case["chunk"]
    assert not ssm.ssm_scan_supported(*args, chunk)

    def counts():
        return {(d, b): _count("dl4j_ssm_scan_calls_total", direction=d,
                               backend=b)
                for d in ("forward", "backward") for b in ("xla", "pallas")}

    before = counts()
    got = _value_and_grads(lambda *a: ssm.ssm_scan(*a, chunk=chunk), args)
    assert counts() == {k: v + (k[1] == "xla") for k, v in before.items()}
    want = _value_and_grads(lambda *a: ssm._chunked(*a, chunk), args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b)


def test_a_sequence_that_is_no_whole_chunks_is_refused():
    with pytest.raises(ValueError, match="no whole number"):
        ssm.ssm_scan(*_scan_args(12), chunk=8)
    with pytest.raises(ValueError, match="no whole number"):
        _make().output(np.zeros((1, 12), np.int32))


def test_the_convolution_is_causal_from_position_zero():
    x = jnp.arange(1.0, 11.0).reshape(1, 5, 2)
    w = jnp.asarray([[1.0, 10.0, 100.0, 1000.0], [0.5, 0.0, 0.0, 2.0]])
    b = jnp.asarray([0.25, -1.0])
    got = ssm.causal_conv1d(x, w, b)
    ch0 = x[0, :, 0]
    # position 0 sees itself under the last tap and zeros before it
    want0 = [0.25 + 1000 * ch0[0],
             0.25 + 100 * ch0[0] + 1000 * ch0[1],
             0.25 + 10 * ch0[0] + 100 * ch0[1] + 1000 * ch0[2],
             0.25 + ch0[0] + 10 * ch0[1] + 100 * ch0[2] + 1000 * ch0[3]]
    _close(got[0, :4, 0], jax.nn.silu(jnp.asarray(want0)))
    _close(got[0, 0, 1], jax.nn.silu(-1.0 + 2.0 * x[0, 0, 1]))


def test_the_gated_norm_gates_first_and_norms_by_group():
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    y, z = (jax.random.normal(k[i], (2, 3, 12)) for i in (0, 1))
    g = 1.0 + 0.1 * jax.random.normal(k[2], (12,))
    got = ssm.gated_group_norm(y, z, g, 3, 1e-5)
    gated = np.asarray(y * jax.nn.silu(z), np.float64).reshape(2, 3, 3, 4)
    want = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 3, 12) * np.asarray(g)
    _close(got, want)
    # norm first and gate after is another function
    other = ssm.gated_group_norm(y, jnp.full_like(z, 1e4), g, 3, 1e-5
                                 ) * jax.nn.silu(z)
    assert float(jnp.abs(other - got).max()) > 0.1


# --------------------------------------------------------------- the router
def _expert_net(held, first, seed=5, d=32, f=24, shared=40, experts=16,
                expert_form="relu2"):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .dtype(zoo.F32)
            .weight_init({"type": "normal", "mean": 0.0, "std": 0.3}).list()
            .layer(RoutedExperts(n_out=d, n_experts=experts,
                                 experts_per_token=3, expert_width=f,
                                 experts_held=held, first_expert=first,
                                 router="sigmoid", routed_scale=2.5,
                                 expert_form=expert_form,
                                 shared_width=shared,
                                 eps=1e-5))
            .layer(TokenOutput(n_out=8, causal=True))
            .set_input_type(InputType.recurrent(d)).build())
    return MultiLayerNetwork(conf).init()


def test_the_bias_moves_the_choice_and_not_the_weights():
    whole = _expert_net(16, 0)
    layer, p = whole.layers[0], whole.params["layer_0"]
    logits = jax.random.normal(jax.random.PRNGKey(4), (40, 16))
    score = np.asarray(jax.nn.sigmoid(logits))
    plain, coef = layer._choose(logits, {"router_bias": jnp.zeros(16)})
    np.testing.assert_allclose(np.asarray(coef).sum(-1), 1.0, rtol=1e-6)
    bias = jnp.zeros(16).at[11].set(5.0)        # expert 11 always chosen
    moved, coef_b = layer._choose(logits, {"router_bias": bias})
    assert np.all((np.asarray(moved) == 11).any(-1))
    assert not np.all((np.asarray(plain) == 11).any(-1))
    # the weights come from the score without the bias
    picked = np.take_along_axis(score, np.asarray(moved), -1)
    _close(coef_b, picked / picked.sum(-1, keepdims=True))
    # through the layer: the chosen experts' weights sum to the scale
    a = jax.random.normal(jax.random.PRNGKey(2), (24, 32))
    c, _, _ = jax.jit(lambda a: ref.routing(
        ref.rms_norm(a, p["ln_g"], 1e-5), p, bias, 3, 0, 2.5))(a)
    np.testing.assert_allclose(np.asarray(c).sum(-1), 2.5, rtol=1e-5)
    assert np.all((np.asarray(c) > 0).sum(-1) == 3)
    assert np.all(np.asarray(c)[:, 11] > 0)
    state = {"layer_0": {**whole.state["layer_0"], "router_bias": bias}}
    want = jax.jit(lambda a: ref.experts(p, state["layer_0"], a,
                                         top_k=3)[0])(a)
    whole.state = state
    _close(whole.feed_forward(a[None])[0][0], want)
    # no gradient reaches the bias
    grads = jax.jit(jax.grad(lambda s: jnp.sum(layer.apply(
        p, {**whole.state["layer_0"], "router_bias": s}, a[None])[0])))(bias)
    assert not np.asarray(grads).any()


def _share(whole, held, first):
    """The net holding ``held`` experts from ``first`` on, with the
    weights ``whole`` (a net holding all of them) has for them."""
    part = _expert_net(held, first)
    p = dict(whole.params["layer_0"])
    for name in ("Wu", "Wd"):
        p[name] = p[name][first:first + held]
    part.params = {**part.params, "layer_0": p}
    return part


def test_the_16_shares_add_up_to_the_uncut_layer():
    """What the 16 chips of a deployment add to a row, each its own
    expert of 16 and every one of them the shared expert, sums to what
    the uncut reference layer adds once the shared expert is counted
    once."""
    whole = _expert_net(16, 0)
    a = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 32), jnp.float32)
    p, s = whole.params["layer_0"], whole.state["layer_0"]
    uncut = jax.jit(lambda a: ref.experts(p, s, a, top_k=3)[0])(a[0]) - a[0]
    shared = sdar_ref._mm(ref.relu2(sdar_ref._mm(
        ref.rms_norm(a[0], p["ln_g"], 1e-5), p["Ws_u"], None)), p["Ws_d"],
        None)
    added = sum(
        np.asarray(_share(whole, 1, first).feed_forward(a)[0][0]) - a[0]
        for first in range(16))
    _close(added - 15 * shared, uncut)
    # and one share is what the reference gives for that share
    part = _share(whole, 2, 6)
    want = jax.jit(lambda a: ref.experts(
        part.params["layer_0"], s, a, top_k=3, first_expert=6)[0])(a[0])
    _close(part.feed_forward(a)[0][0], want)


# --------------------------------------------------------- relu2 experts
def _dense_relu2(x, rows, coef, counts, wg, wu, wd):
    y, start = jnp.zeros(x.shape, jnp.float32), 0
    for e, c in enumerate(np.asarray(counts)):
        idx = rows[start:start + c]
        out = ref.relu2(x[idx] @ wu[e]) @ wd[e]
        y = y.at[idx].add(out * coef[start:start + c, None])
        start += c
    return y


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_relu2_experts_match_a_dense_product(monkeypatch, case):
    """``expert_ffn`` without a gate matrix against a per-expert ``jnp``
    product, over the count patterns the gated kernels are held to:
    forward and the gradients in x, coef, Wu, Wd. The chunk loop runs
    it, in interpret mode too: the kernels are the gated form's."""
    counts, elsewhere, chunk = KERNEL_CASES[case]
    rows, d, f = 512, 128, 128
    x, idx, coef, counts_a, _, wu, wd = _pairs(counts, elsewhere, rows, d, f)
    g = jax.random.normal(jax.random.PRNGKey(9), (rows, d), jnp.float32)

    def value_and_grads(fn):
        def loss(x, coef, wu, wd):
            return jnp.sum(fn(x, idx, coef, counts_a, None, wu, wd) * g)
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3)))(
            x, coef, wu, wd)

    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    assert not grouped.grouped_supported(x, None, wu, wd, len(idx), chunk)
    got = value_and_grads(
        lambda *a: grouped.expert_ffn(*a, chunk=chunk))
    want = value_and_grads(_dense_relu2)
    assert abs(got[0] - want[0]) <= RTOL * max(abs(want[0]), 1e-30)
    for a, b in zip(got[1], want[1]):
        _close(a, b)
    assert not np.asarray(got[1][1])[sum(counts):].any()


@pytest.mark.parametrize("why,d,f,interpret", [
    ("whole lane tiles, interpret mode: no gate", 128, 256, "1"),
    ("a width of 14.5 lane tiles", 128, 232, "1"),
    ("a hidden size off the lane tile", 96, 128, "1"),
    ("no TPU and no interpret mode", 128, 256, "0")])
def test_relu2_experts_take_the_chunk_loop(monkeypatch, why, d, f,
                                           interpret):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", interpret)
    before = _count("dl4j_moe_grouped_matmul_calls_total",
                    backend="xla_chunks")
    layer_net = _expert_net(2, 0, d=d, f=f, shared=0, experts=4)
    layer_net.output(np.zeros((1, 4, d), np.float32))
    assert _count("dl4j_moe_grouped_matmul_calls_total",
                  backend="xla_chunks") == before + 1, why
    assert "Wg" not in layer_net.params["layer_0"]


def test_routed_experts_refuse_an_unknown_form():
    with pytest.raises(ValueError, match="expert_form 'geglu' is neither"):
        _expert_net(2, 0, d=8, f=8, shared=0, experts=4,
                    expert_form="geglu")


# ------------------------------------------------------- causal attention
@pytest.mark.parametrize("rows,bq,bk", [(512, 128, 128), (512, 128, 256),
                                        (1024, 128, 512), (384, 128, 128),
                                        (2048, 512, 512), (1024, 256, 512)])
def test_causal_live_tiles_are_those_with_a_visible_entry(rows, bq, bk):
    i = np.arange(rows)
    visible = i[:, None] >= i[None, :]
    tiles = visible.reshape(rows // bq, bq, rows // bk, bk).any((1, 3))
    live = att._causal_live_tiles(rows, bq, bk)
    assert {(q, k) for q, k, _ in live} == set(zip(*np.nonzero(tiles)))
    assert set(live[:, 2]) == {2}
    # and inside a tile the kernels' rule is j <= i
    kb, lower, upper = att._bd_bounds(
        2, 128, 0, rows, 0, jnp.arange(bq)[:, None], jnp.arange(bk)[None, :])
    np.testing.assert_array_equal(
        np.asarray((kb <= upper) & (kb >= lower)), visible[128:128 + bq, :bk])


@pytest.mark.parametrize("why,heads,kv_heads,dh,seq,block,want", [
    ("sdar_30b_a3b-train-b1-l4096", 32, 4, 128, 4096, 4, 128),
    ("nemotron3_nano_30b_a3b-train-b1-l4096", 32, 2, 128, 4096, None, 128),
    ("glm4_7_flash-train-b1-l4096", 20, 20, 256, 4096, None, 512),
    ("a group of 4", 8, 2, 128, 4096, None, 256),
    ("a group of 2 stops at the key tile", 4, 2, 128, 4096, None, 512),
    ("384 rows hold no tile of 256 or 512", 2, 2, 128, 384, None, 128),
    ("halves of 768 rows hold tiles of 256", 2, 2, 128, 768, 4, 256)])
def test_query_tile_follows_the_group(why, heads, kv_heads, dh, seq, block,
                                      want):
    """The tiled kernels' query tile is a function of the group and the
    sequence (no option selects it): about 1,024 rows a grid step, so
    the two accepted decoder cells keep the tile of 128 they had and a
    group of 1 gets 512. Traced at the cells' own shapes, nothing runs;
    the counter says which tile each direction was given."""
    group = heads // kv_heads
    assert att._bd_query_tile(group, seq) == want, why
    rows = seq if block is None else 2 * seq
    q, k, v = (jax.ShapeDtypeStruct((1, rows, h, dh), jnp.bfloat16)
               for h in (heads, kv_heads, kv_heads))

    def attend(q, k, v):
        qg, kg, vg = att._bd_split(q, k, v)
        og = (att._causal_tiled(qg, kg, vg) if block is None
              else att._bd_attention(qg, kg, vg, seq, block))
        return jnp.sum(att._bd_join(og, 1).astype(jnp.float32))

    def counts():
        return {(d, tile): _count(
            "dl4j_tiled_attention_calls_total", direction=d,
            group=str(group), query_tile=str(tile))
            for d in ("forward", "backward") for tile in (128, 256, 512)}

    before = counts()
    jax.eval_shape(jax.grad(attend, (0, 1, 2)), q, k, v)
    after = counts()
    assert {key: after[key] - before[key] for key in after} == {
        (d, tile): float(tile == want)
        for d in ("forward", "backward") for tile in (128, 256, 512)}, why


@pytest.mark.parametrize("why,heads,kv_heads,dh,seq,block,form", [
    ("sdar_30b_a3b-train-b1-l4096", 32, 4, 128, 4096, 4, "fused"),
    ("nemotron3_nano_30b_a3b-train-b1-l4096", 32, 2, 128, 4096, None,
     "fused"),
    ("glm4_7_flash-train-b1-l4096", 20, 20, 256, 4096, None, "fused"),
    ("lfm2_24b_a2b-train-b1-l8192", 32, 8, 64, 8192, None, "fused"),
    ("32k rows: dK and dV of a head pass the budget", 32, 4, 128, 32768,
     None, "split")])
def test_backward_form_follows_the_resident_bytes(why, heads, kv_heads, dh,
                                                  seq, block, form):
    """One backward kernel where a key/value head's dK and dV fit in VMEM
    beside their output buffers (every decoder cell's shape), the dQ and
    dK/dV kernels where they do not: a function of the call's shapes, no
    option selects it. Traced at the shapes, nothing runs; every
    backward is still counted in ``dl4j_tiled_attention_calls_total``."""
    group = heads // kv_heads
    rows = seq if block is None else 2 * seq
    assert att._bd_fused_fits(rows, dh, jnp.bfloat16) == (form == "fused")
    q, k, v = (jax.ShapeDtypeStruct((1, rows, h, dh), jnp.bfloat16)
               for h in (heads, kv_heads, kv_heads))

    def attend(q, k, v):
        qg, kg, vg = att._bd_split(q, k, v)
        og = (att._causal_tiled(qg, kg, vg) if block is None
              else att._bd_attention(qg, kg, vg, seq, block))
        return jnp.sum(att._bd_join(og, 1).astype(jnp.float32))

    def counts():
        return {f: _count("dl4j_tiled_attention_backward_total", form=f,
                          group=str(group)) for f in ("fused", "split")}

    tile = str(att._bd_query_tile(group, seq))
    calls = _count("dl4j_tiled_attention_calls_total", direction="backward",
                   group=str(group), query_tile=tile)
    before = counts()
    jax.eval_shape(jax.grad(attend, (0, 1, 2)), q, k, v)
    after = counts()
    assert {f: after[f] - before[f] for f in after} == {
        f: float(f == form) for f in after}, why
    assert _count("dl4j_tiled_attention_calls_total", direction="backward",
                  group=str(group), query_tile=tile) == calls + 1


@pytest.mark.parametrize("rows,block,heads,kv_heads", [
    (1024, None, 16, 2), (512, 4, 8, 2)], ids=["causal", "block_diffusion"])
def test_split_backward_is_the_fused_ones(monkeypatch, rows, block, heads,
                                          kv_heads):
    """Where the resident dK and dV would pass the budget the backward
    is the dQ and the dK/dV kernels: the same gradients on one input,
    within the order in which dQ's products are summed."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    seq = rows if block is None else rows // 2
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(k[0], (1, rows, heads, 128), jnp.float32)
    kk, v = (jax.random.normal(k[i], (1, rows, kv_heads, 128), jnp.float32)
             for i in (1, 2))
    g = jax.random.normal(k[3], q.shape, jnp.float32)

    def grads():
        def loss(*a):
            qg, kg, vg = att._bd_split(*a)
            og = (att._causal_tiled(qg, kg, vg) if block is None
                  else att._bd_attention(qg, kg, vg, seq, block))
            return jnp.sum(att._bd_join(og, 1) * g)
        return jax.jit(jax.grad(loss, (0, 1, 2)))(q, kk, v)

    group = str(heads // kv_heads)
    fused = grads()
    # a budget this sequence's resident blocks pass
    monkeypatch.setattr(att, "_BD_RESIDENT_BUDGET",
                        2 * rows * 128 * 12 - 1)
    assert not att._bd_fused_fits(rows, 128, jnp.float32)
    before = _count("dl4j_tiled_attention_backward_total", form="split",
                    group=group)
    split = grads()
    assert _count("dl4j_tiled_attention_backward_total", form="split",
                  group=group) == before + 1
    for a, b in zip(fused, split):
        _close(a, b, rtol=1e-5)


def _dense_causal(q, k, v):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bihd,bjhd->bhij", q, k) / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])
    p = jax.nn.softmax(jnp.where(i[:, None] >= i[None, :], s, -jnp.inf), -1)
    return jnp.einsum("bhij,bjhd->bihd", p, v)


@pytest.mark.parametrize("backend,rows,dh,heads,kv_heads", [
    ("pallas", 512, 128, 4, 2), ("xla", 24, 16, 4, 2),
    # the cell's group, 16 query heads a key/value head (32 on 2), and a
    # query tile that meets two key tiles, so the running max and sum are
    # rescaled
    ("pallas", 1024, 128, 16, 1),
    # the latent decoder cell's group of 1 and heads of 256: a query tile
    # of 512 that meets two key tiles and a diagonal tile; and a group of
    # 4, a tile of 256 (two query tiles a key tile)
    ("pallas", 1024, 256, 2, 2), ("pallas", 512, 128, 4, 1),
    # the fused backward over two key/value heads at groups of 16 and 8:
    # a key tile meets several query tiles of 128 (dK, dV resident across
    # their runs), a query tile two or three key tiles (dQ across steps);
    # 768 rows take key tiles of 256
    ("pallas", 768, 128, 32, 2), ("pallas", 1024, 128, 16, 2)],
    ids=["pallas-512-128", "xla-24-16", "pallas-1024-128-16on1",
         "pallas-1024-256-1on1", "pallas-512-128-4on1",
         "pallas-768-128-16on1x2", "pallas-1024-128-8on1x2"])
def test_causal_attention_matches_dense_masked_softmax(monkeypatch, backend,
                                                       rows, dh, heads,
                                                       kv_heads):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(k[0], (1, rows, heads, dh), jnp.float32)
    kk, v = (jax.random.normal(k[i], (1, rows, kv_heads, dh), jnp.float32)
             for i in (1, 2))
    g = jax.random.normal(k[3], q.shape, jnp.float32)
    assert att.causal_attention_supported(q, kk, v) == (backend == "pallas")
    before = _count("dl4j_causal_attention_calls_total", backend=backend)

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * g),
                                          (0, 1, 2)))(q, kk, v)

    got = value_and_grads(att.causal_attention)
    want = value_and_grads(_dense_causal)
    assert _count("dl4j_causal_attention_calls_total",
                  backend=backend) > before
    assert abs(got[0] - want[0]) <= 1e-4 * abs(want[0])
    for a, b in zip(got[1], want[1]):
        _close(a, b, rtol=1e-4)


# ------------------------------------------------------------ the net path
def test_fit_scan_of_8_equals_eight_single_steps():
    ring = [_batch(seed) for seed in range(8)]
    one, eight = _make(2, learning_rate=1e-3), _make(2, learning_rate=1e-3)
    for ds in ring:
        one.fit_batch(ds)
    eight.fit(ListDataSetIterator(ring), multi_step=8, device_prefetch=True)
    assert eight.iteration == one.iteration == 8
    for a, b in zip(jax.tree_util.tree_leaves(one.params),
                    jax.tree_util.tree_leaves(eight.params)):
        _close(b, a, rtol=1e-6)
    np.testing.assert_array_equal(
        one.state["layer_2"]["expert_rows_total"],
        eight.state["layer_2"]["expert_rows_total"])
    assert abs(float(one.score_value) - float(eight.score_value)) < 1e-5


def test_fit_with_default_arguments_lowers_the_loss_on_integer_ids(net):
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


def test_every_op_of_the_step_is_placed_under_a_scope(net, batch):
    step = jax.jit(net._step_fn())
    args = net._step_args(net._batch_args(batch), jax.random.PRNGKey(0))
    index = opindex.parse(step.lower(*args).compile().as_text())
    seen = set()
    for entry in index.values():
        phase, layer, _ = opindex.place(entry)
        if entry["opcode"] in ("fusion", "custom-call", "dot", "scatter",
                               "gather", "sort", "while"):
            assert phase != "unplaced", entry
        seen.add((phase, opindex.place(entry, scopes=SCOPES)[1]))
    for scope in SCOPES:
        assert ("forward", scope) in seen and ("backward", scope) in seen


def test_trace_time_counters_and_the_collector(batch):
    before = {d: _count("dl4j_ssm_scan_calls_total", direction=d,
                        backend="xla") for d in ("forward", "backward")}
    fresh = _make(3)
    fresh.fit_batch(batch)
    # two Mamba layers, each traced forward and backward
    for direction, was in before.items():
        assert _count("dl4j_ssm_scan_calls_total", direction=direction,
                      backend="xla") >= was + 2
    counted = obs_moe.expert_rows(fresh)
    assert sorted(counted) == ["layer_2", "layer_5"]
    last, total = counted["layer_5"]
    assert last.shape == (4,) and int(total.sum()) == int(last.sum())
    # three experts a row, four of sixteen held: about 2 L * 3 / 4 pairs
    assert 0 < int(last.sum()) < 2 * L * 3
    assert np.asarray(fresh.state["layer_2"]["router_bias"]).shape == (16,)


def test_the_block_diffusion_decoder_is_as_it_was():
    """The SDAR net's defaults, names, init and loss do not move with
    this file's layers: its parameters are what its own recipe of keys
    gives, and its loss is its reference's."""
    small = dict(n_layers=1, n_experts=8, experts_held=4, vocab_size=32,
                 hidden=16, n_heads=2, n_kv_heads=1, head_dim=8,
                 expert_width=12, experts_per_token=2, dtype=zoo.F32)
    sdar = zoo.sdar_moe(seed=3, **small)
    p = sdar.params["layer_1"]
    assert sorted(p) == sorted([
        "ln_g", "Wr", "Wg", "Wu", "Wd", "attn_ln_g", "Wq", "Wk", "Wv", "Wo",
        "q_norm_g", "k_norm_g"])
    assert sorted(sdar.state["layer_1"]) == ["expert_rows",
                                             "expert_rows_total"]
    conf = sdar.layers[1].conf
    assert (conf.router, conf.expert_form, conf.shared_width,
            conf.routed_scale) == ("softmax", "gated_silu", 0, 1.0)
    assert sdar.layers[-1].conf.causal is False
    ds = BlockDiffusionPreProcessor(4, 31, seed=3).pre_process(
        DataSet(np.random.default_rng(0).integers(0, 31, (2, 16))))
    want = jax.jit(lambda p: sdar_ref.loss(
        p, sdar.state, ds.features, ds.labels, ds.labels_mask, top_k=2))(
            sdar.params)
    assert abs(sdar.score(ds) - float(want)) <= RTOL * float(want)
