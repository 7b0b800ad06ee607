"""The latent-attention decoder of zoo.glm4_moe_lite on the net's own
path, against the plain reference the benchmark holds it to
(benchmark/reference/glm4_moe_lite.py), at small widths on the CPU with
seeded weights: latent attention, a leading dense layer, sigmoid-routed
gated experts beside a shared expert, and a multi-token-prediction
module that shares the embedding and the head.

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

from benchmark.reference import glm4_moe_lite as ref
from deeplearning4j_tpu import (
    MultiLayerNetwork, NeuralNetConfiguration, zoo)
from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers_decoder import RoutedExperts
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.observability import moe as obs_moe
from deeplearning4j_tpu.observability import opindex
from deeplearning4j_tpu.ops import grouped
from tests.test_sdar_moe import _count, _pairs

RTOL = 2e-5
VOCAB, L, D = 64, 32, 32
SMALL = dict(n_layers=3, first_dense=1, n_experts=16, experts_held=4,
             first_expert=4, vocab_size=VOCAB, hidden=D, n_heads=2,
             q_rank=16, kv_rank=8, nope_dim=12, rope_dim=4, v_dim=16,
             mlp_width=48, expert_width=24, shared_width=24,
             experts_per_token=3, dtype=zoo.F32)
HOW = dict(top_k=3, first_expert=4, heads=2, nope=12)
OUT = "layer_4"
SCOPES = ("attn", "mla_down", "mla_up", "causal_attention", "dense_mlp",
          "route", "experts", "shared_expert", "head", "mtp")


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= rtol * max(np.abs(b).max(), 1e-30), (
        np.abs(a - b).max(), np.abs(b).max())


def _make(seed=1, **more):
    return zoo.glm4_moe_lite(seed=seed, **{**SMALL, **more})


def _batch(seed=0, rows=2):
    ids = np.random.default_rng(seed).integers(0, VOCAB, (rows, L + 2),
                                               dtype=np.int32)
    return DataSet(ids[:, :L], np.stack([ids[:, 1:L + 1], ids[:, 2:]], 1))


@pytest.fixture(scope="module")
def net():
    return _make(learning_rate=3e-3)


@pytest.fixture(scope="module")
def batch():
    return _batch()


def _both_logits(net, batch):
    """The program's two logit arrays: ``net.output`` and the module's
    through the output layer's own methods."""
    out = net.layers[-1]
    p = net._layer_params(out, net.params)
    h = net.feed_forward(batch.features)[-2]
    _, g, _ = jax.jit(out.module)(p, net.state[OUT], h,
                                  jnp.asarray(batch.labels[:, 0]))
    return net.output(batch.features), out._logits(p, "mtp_norm_g", g)


# ------------------------------------------------ system against reference
@pytest.mark.parametrize("first_dense", [0, 1, 2])
def test_the_leading_layers_are_dense_and_the_rest_expert_layers(
        first_dense):
    net = _make(first_dense=first_dense)
    kinds = [type(layer.conf).__name__ for layer in net.layers]
    assert kinds == (["TokenEmbedding"] + ["LatentDenseBlock"] * first_dense
                     + ["LatentMoeBlock"] * (3 - first_dense)
                     + ["MtpTokenOutput"])
    assert [ref.kind_of(net.params[f"layer_{i}"]) for i in range(1, 5)] == \
        list("D" * first_dense + "E" * (3 - first_dense) + "M")
    with pytest.raises(ValueError, match="dense layers among"):
        _make(first_dense=4)
    with pytest.raises(ValueError, match="one or none"):
        _make(mtp_modules=2)


def test_without_the_module_the_head_is_the_plain_one():
    plain = _make(mtp_modules=0)
    assert [type(layer.conf).__name__ for layer in plain.layers[-2:]] == [
        "RmsNorm", "TokenOutput"]
    ds = _batch()
    plain.fit_batch(DataSet(ds.features, ds.labels[:, 0]))
    assert obs_moe.mtp_losses(plain) is None


def test_logits_match_the_reference(net, batch):
    want = jax.jit(lambda p: ref.both_logits(
        p, net.state, batch.features, batch.labels[:, 0], **HOW))(net.params)
    got = _both_logits(net, batch)
    assert got[0].shape == got[1].shape == (2, L, VOCAB)
    _close(got[0], want[0])
    _close(got[1], want[2])
    _close(got[0], jax.jit(lambda p: ref.logits(
        p, net.state, batch.features, **HOW))(net.params))


def test_both_losses_match_the_reference(net, batch):
    want, (parts, *_) = jax.jit(lambda p: ref.loss(
        p, net.state, batch.features, batch.labels, with_logits=True,
        **HOW))(net.params)
    assert abs(net.score(batch) - float(want)) <= RTOL * float(want)
    _, state = jax.jit(lambda p: net._loss(
        p, net.state, *net._batch_args(batch), None))(net.params)
    _close(state[OUT]["mtp_loss"], parts)
    assert abs(float(parts[0] + 0.3 * parts[1]) - float(want)) < 1e-6
    # a weight per label, [b, 2, t] like the labels
    weights = np.random.default_rng(1).uniform(0.5, 1.5, batch.labels.shape
                                               ).astype(np.float32)
    weighed = DataSet(batch.features, batch.labels, labels_mask=weights)
    want = jax.jit(lambda p: ref.loss(
        p, net.state, batch.features, batch.labels, weights, **HOW))(
            net.params)
    assert abs(net.score(weighed) - float(want)) <= RTOL * float(want)
    with pytest.raises(TypeError, match=r"\[b, 2, t\]"):
        net.score(DataSet(batch.features, batch.labels[:, 0]))


@pytest.fixture(scope="module")
def gradients(net, batch):
    x, y = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    got = jax.jit(jax.grad(lambda p: net._loss(
        p, net.state, x, y, None, None, None)[0]))(net.params)
    want = {weight: jax.jit(jax.grad(lambda p: ref.loss(
        p, net.state, x, y, mtp_weight=weight, **HOW)))(net.params)
        for weight in (0.3, 0.0, 1.0)}
    return got, want


ATTENTION = ["attn_ln_g", "W_dq", "q_ln_g", "W_uq", "W_dkv", "kv_ln_g",
             "W_ukv", "Wo"]
EXPERTS = ["ln_g", "Wr", "Wg", "Wu", "Wd", "Ws_g", "Ws_u", "Ws_d"]
GROUPS = [(f"layer_{i}", leaf) for i, leaves in {
    0: ["W"],
    1: ATTENTION + ["ln_g", "Wg", "Wu", "Wd"],
    3: ATTENTION + EXPERTS,
    4: ATTENTION + EXPERTS + ["norm_g", "W", "enorm_g", "hnorm_g",
                              "mtp_norm_g", "W_eh"]}.items()
    for leaf in leaves]


@pytest.mark.parametrize("layer,name", GROUPS,
                         ids=[f"{l}.{n}" for l, n in GROUPS])
def test_gradient_of_every_parameter_group(net, gradients, layer, name):
    got, want = gradients
    assert sorted(got[layer]) == sorted(net.params[layer])
    assert float(jnp.abs(want[0.3][layer][name]).max()) > 0
    # the gradient of a sum of hundreds of terms, each side in its order
    _close(got[layer][name], want[0.3][layer][name], rtol=1e-4)


@pytest.mark.parametrize("layer", ["layer_0", OUT])
def test_a_shared_leaf_holds_the_sum_of_its_two_users(net, gradients,
                                                      layer):
    """The embedding's matrix and the head's are read by the model and
    by the module: the stored leaf's gradient is the model's (what the
    loss without the module gives) and 0.3 of the module's."""
    got, want = gradients
    model = want[0.0][layer]["W"]
    module = want[1.0][layer]["W"] - model
    assert float(jnp.abs(module).max()) > 0.05 * float(jnp.abs(model).max())
    _close(got[layer]["W"], model + 0.3 * module, rtol=1e-4)
    # dropping the module's share would be seen
    assert np.abs(np.asarray(got[layer]["W"] - model)).max() > 100 * 1e-4 * \
        np.abs(np.asarray(model)).max()


def test_a_shared_leaf_is_stored_and_counted_once(net):
    assert net.layers[-1].shares == {"Emb": ("layer_0", "W")}
    assert "Emb" not in net.params[OUT]
    assert sorted(net.opt_state) == sorted(net.params)
    attention = D * 16 + 16 * 2 * 16 + D * (8 + 4) + 8 * 2 * 28 + 32 * D \
        + D + 16 + 8
    experts = D + D * 16 + 4 * 3 * D * 24 + 3 * D * 24
    assert net.num_params() == (
        VOCAB * D                                   # the embedding, once
        + attention + D + 3 * D * 48                # the dense layer
        + 2 * (attention + experts)
        + attention + experts + 2 * D * D + 4 * D   # the module
        + D * VOCAB)                                # the head, once


# --------------------------------------------------------- latent attention
def _attention_inputs():
    net = _make(n_layers=1, first_dense=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (L, D), jnp.float32)
    return net.layers[1], net.params["layer_1"], x


def test_latent_attention_is_a_dense_masked_softmax_over_built_keys():
    layer, p, x = _attention_inputs()
    q, k, v = ref.heads_of(p, x, heads=2, nope=12)
    assert q.shape == k.shape == v.shape == (L, 2, 16)
    # one rotated key slice for all heads, written out for each
    np.testing.assert_array_equal(k[:, 0, 12:], k[:, 1, 12:])
    s = jnp.einsum("ihd,jhd->hij", q, k) / 4.0          # sqrt(12 + 4)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, axis=-1), v)
    want = x + o.reshape(L, -1) @ p["Wo"]
    _close(ref.latent_attention(p, x, heads=2, nope=12), want)
    _close(layer._latent_attention(p, x[None])[0], want)


def test_the_rotation_moves_the_rope_columns_only():
    layer, p, x = _attention_inputs()
    at = {theta: ref.heads_of(p, x, heads=2, nope=12, theta=theta)
          for theta in (1e6, 10.0)}
    for a, b in zip(at[1e6], at[10.0]):
        np.testing.assert_array_equal(a[..., :12], b[..., :12])
        np.testing.assert_array_equal(a[0], b[0])       # position 0
    assert float(jnp.abs(at[1e6][0][1:, :, 12:]
                         - at[10.0][0][1:, :, 12:]).max()) > 1e-3
    np.testing.assert_array_equal(at[1e6][2], at[10.0][2])      # values
    # a pair of columns (i, i + rope / 2) keeps its length
    plain = (ref.rms_norm(ref.rms_norm(x, p["attn_ln_g"], 1e-5) @ p["W_dq"],
                          p["q_ln_g"], 1e-5) @ p["W_uq"]).reshape(L, 2, 16)
    turned = at[1e6][0]
    for i in (12, 13):
        _close(turned[..., i] ** 2 + turned[..., i + 2] ** 2,
               plain[..., i] ** 2 + plain[..., i + 2] ** 2, rtol=1e-4)
    # and the program turns with the same base
    other = _make(n_layers=1, rope_theta=10.0)
    _close(other.feed_forward(np.arange(L, dtype=np.int32)[None])[1][0],
           ref.block("D", other.params["layer_1"], {},
                     other.params["layer_0"]["W"][:L], heads=2, nope=12,
                     theta=10.0)[0])


def test_heads_of_another_size_for_values_are_refused():
    with pytest.raises(ValueError, match="one head size"):
        _make(v_dim=8)


# ------------------------------------------------------------- the module
def test_the_module_reads_the_next_token_and_never_the_one_it_predicts(
        net, batch):
    main, ahead = _both_logits(net, batch)
    later = batch.labels.copy()
    later[:, 1] = (later[:, 1] + 7) % VOCAB
    again = _both_logits(net, DataSet(batch.features, later))
    np.testing.assert_array_equal(main, again[0])
    np.testing.assert_array_equal(ahead, again[1])
    parts = lambda ds: np.asarray(net._loss(
        net.params, net.state, *net._batch_args(ds), None)[1][OUT][
            "mtp_loss"])
    was, now = parts(batch), parts(DataSet(batch.features, later))
    assert was[0] == now[0] and was[1] != now[1]
    # token i + 1 enters at row i, and attention is causal: rows before
    # it do not move
    nearer = batch.labels.copy()
    nearer[:, 0, 20] = (nearer[:, 0, 20] + 7) % VOCAB
    moved = _both_logits(net, DataSet(batch.features, nearer))
    np.testing.assert_array_equal(main, moved[0])
    np.testing.assert_array_equal(ahead[:, :20], moved[1][:, :20])
    assert np.abs(np.asarray(ahead[:, 20:] - moved[1][:, 20:])).max() > 1e-4


def test_the_module_is_the_references(net, batch):
    out = net.layers[-1]
    h = net.feed_forward(batch.features)[-2]
    given, g, _ = jax.jit(out.module)(
        net._layer_params(out, net.params), net.state[OUT], h,
        jnp.asarray(batch.labels[:, 0]))
    want = jax.jit(lambda p: ref.module(
        p[OUT], net.state[OUT], p["layer_0"]["W"], h[0], batch.labels[0, 0],
        **HOW))(net.params)
    _close(given[0], want[0])
    _close(g[0], want[1])


# ------------------------------------------------------------ the experts
def _expert_net(held, first, d=32, experts=16):
    conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
            .dtype(zoo.F32)
            .weight_init({"type": "normal", "mean": 0.0, "std": 0.3})
            .list()
            .layer(RoutedExperts(
                n_out=d, n_experts=experts, experts_per_token=3,
                expert_width=24, experts_held=held, first_expert=first,
                eps=1e-5, router="sigmoid", routed_scale=1.8,
                expert_form="gated_silu", shared_width=40))
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
    experts of 16 and every one of them the shared expert, sums to what
    the uncut reference layer adds once the shared expert is counted
    once."""
    whole = _expert_net(16, 0)
    a = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 32), jnp.float32)
    p, s = whole.params["layer_0"], whole.state["layer_0"]
    assert sorted(p) == sorted(EXPERTS)
    uncut = jax.jit(lambda a: ref.experts(p, s, a, top_k=3)[0])(a[0]) - a[0]
    shared = ref._gated(ref.rms_norm(a[0], p["ln_g"], 1e-5), p["Ws_g"],
                        p["Ws_u"], p["Ws_d"], None)
    added = sum(
        np.asarray(_share(whole, 2, first).feed_forward(a)[0][0]) - a[0]
        for first in range(0, 16, 2))
    _close(added - 7 * shared, uncut)
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
    np.testing.assert_allclose(np.asarray(coef).sum(-1), 1.0, rtol=1e-6)
    bias = jnp.zeros(16).at[11].set(5.0)        # expert 11 always chosen
    moved, coef_b = layer._choose(logits, {"router_bias": bias})
    assert np.all((np.asarray(moved) == 11).any(-1))
    assert not np.all((np.asarray(plain) == 11).any(-1))
    # the weights come from the score without the bias
    picked = np.take_along_axis(score, np.asarray(moved), -1)
    _close(coef_b, picked / picked.sum(-1, keepdims=True))
    # through the layer: scaled by 1.8, as the reference has it
    a = jax.random.normal(jax.random.PRNGKey(2), (24, 32))
    state = {**whole.state["layer_0"], "router_bias": bias}
    want = jax.jit(lambda a: ref.experts(p, state, a, top_k=3)[0])(a)
    whole.state = {"layer_0": state}
    _close(whole.feed_forward(a[None])[0][0], want)
    # no gradient reaches the bias
    grads = jax.jit(jax.grad(lambda s: jnp.sum(layer.apply(
        p, {**state, "router_bias": s}, a[None])[0])))(bias)
    assert not np.asarray(grads).any()


# ------------------------------------------- the hidden width in slices
def test_a_width_that_fits_takes_one_slice_and_the_next_two():
    assert grouped._width_slices(2048, 768, 2) == 1
    assert grouped._width_slices(2048, 1536, 2) == 2
    assert grouped._vmem_request(2048, 1536, 2) > grouped._VMEM_CAP
    # whole lane tiles, and equal ones: 1,856 is 14.5 tiles, 5 tiles of
    # a width that only fits in halves cannot be halved
    assert grouped._width_slices(2688, 1856, 2) == 0
    assert grouped._width_slices(2048, 5 * 128, 2) == 1


@pytest.mark.parametrize("case", ["one_block", "two_blocks"])
def test_a_width_split_expert_ffn_is_the_unsplit_one(monkeypatch, case):
    counts, chunk = {"one_block": ((120, 260, 7, 50), 256),
                     "two_blocks": ((400, 300, 200, 100), 128)}[case]
    args = _pairs(counts, 90, 512, 128, 512)
    g = jnp.asarray(np.random.default_rng(1).normal(size=(512, 128)),
                    jnp.float32)

    def value_and_grads():
        def loss(x, coef, wg, wu, wd):
            return jnp.sum(grouped.expert_ffn(
                x, args[1], coef, args[3], wg, wu, wd, chunk=chunk) * g)
        return jax.value_and_grad(loss, (0, 1, 2, 3, 4))(
            args[0], args[2], *args[4:])

    def ran(backend):
        return _count("dl4j_moe_grouped_matmul_calls_total", backend=backend)

    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    assert grouped._width_slices(128, 512, 4) == 1
    before = ran("pallas")
    whole = value_and_grads()
    # a cap that two of four lane tiles fit under, and four do not
    monkeypatch.setattr(grouped, "_VMEM_CAP",
                        grouped._vmem_request(128, 256, 4))
    assert grouped._width_slices(128, 512, 4) == 2
    assert len(grouped._sliced(*args[4:])) == 2
    assert grouped.grouped_supported(args[0], *args[4:], len(args[1]), chunk)
    split = value_and_grads()
    assert ran("pallas") == before + 2
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "0")
    loop = value_and_grads()
    for want in (whole, loop):
        assert abs(split[0] - want[0]) <= RTOL * abs(want[0])
        for a, b in zip(split[1], want[1]):
            _close(a, b)


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
    for name in ("layer_2", OUT):
        np.testing.assert_array_equal(
            one.state[name]["expert_rows_total"],
            eight.state[name]["expert_rows_total"])
    _close(eight.state[OUT]["mtp_loss"], one.state[OUT]["mtp_loss"],
           rtol=1e-6)
    assert abs(float(one.score_value) - float(eight.score_value)) < 1e-5


def test_fit_with_default_arguments_lowers_both_losses(net):
    ring = [_batch(seed) for seed in (4, 5)]
    assert ring[0].features.dtype == ring[0].labels.dtype == np.int32
    before = [net.score(ds) for ds in ring]
    net.fit(ListDataSetIterator(ring[:1]))
    first = obs_moe.mtp_losses(net)
    net.fit(ListDataSetIterator(ring * 4))
    assert all(net.score(ds) < b for ds, b in zip(ring, before))
    last = obs_moe.mtp_losses(net)
    assert last[0] < first[0] and last[1] < first[1]
    assert abs(float(net.score_value) - (last[0] + 0.3 * last[1])) < 1e-5
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
    assert again.layers[-1].embedding == "layer_0"


def test_save_and_load_keep_one_copy_of_a_shared_leaf(net, batch, tmp_path):
    from deeplearning4j_tpu.utils.serialization import (
        restore_multi_layer_network, write_model)
    path = str(tmp_path / "glm.zip")
    write_model(net, path)
    with zipfile.ZipFile(path) as zf:
        saved = list(np.load(io.BytesIO(zf.read("coefficients.npz"))))
    assert not [k for k in saved if "Emb" in k]
    assert len([k for k in saved if k.endswith("['W']")]) == 2
    assert len(saved) == len(jax.tree_util.tree_leaves(net.params))
    again = restore_multi_layer_network(path)
    np.testing.assert_array_equal(again.output(batch.features),
                                  net.output(batch.features))
    assert again.score(batch) == net.score(batch)
    assert again.num_params() == net.num_params()


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
        # what the module's layers do is under mtp whatever lies inside
        if opindex.place(entry, scopes=("mtp",))[1] == "mtp":
            assert layer == OUT
    for scope in SCOPES:
        assert ("forward", scope) in seen and ("backward", scope) in seen


def test_trace_time_counters_and_the_collector(batch):
    from deeplearning4j_tpu.observability.metrics import get_registry
    before = _count("dl4j_mla_layers_traced_total")
    attention = _count("dl4j_causal_attention_calls_total",
                       direction="forward")
    fresh = _make(3)
    fresh.fit(ListDataSetIterator([batch]))
    # three layers and the module's, each traced once by the step
    assert _count("dl4j_mla_layers_traced_total") >= before + 4
    assert _count("dl4j_causal_attention_calls_total",
                  direction="forward") >= attention + 4
    counted = obs_moe.expert_rows(fresh)
    assert sorted(counted) == ["layer_2", "layer_3", OUT]
    last, total = counted[OUT]
    assert last.shape == (4,) and int(total.sum()) == int(last.sum())
    # three experts a row, four of sixteen held: about 2 L * 3 / 4 pairs
    assert 0 < int(last.sum()) < 2 * L * 3
    main, ahead = obs_moe.mtp_losses(fresh)
    assert 3.5 < main < 5.5 and 3.5 < ahead < 5.5 and main != ahead
    gauge = {s.labels["part"]: s.value
             for family in get_registry().collect()
             if family.name == "dl4j_mtp_loss" for s in family.samples
             if s.labels["layer"] == OUT}
    assert set(gauge) == {"main", "mtp"}
