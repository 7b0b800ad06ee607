"""The sparse-attention decoder of zoo.keye_vl2_moe on the net's own
path, against the plain reference the benchmark holds it to
(benchmark/reference/keye_vl2_moe.py), at small widths on the CPU with
seeded weights: grouped-query attention over the keys a learned indexer
selects for each row, the indexer's own loss beside the data loss, and
softmax-routed gated experts. The kernels of ops/sparse_attention.py run
in interpret mode against its XLA forms.

Tolerances: the float32 policy runs the same mathematics as the
reference in another order, so the two agree to float32 rounding of
sums of tens to hundreds of terms: 2e-5 relative to the largest entry
compared (1e-4 for gradients). The suite runs with x64 on; every array
here is float32 by construction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2_moe as ref
from deeplearning4j_tpu import zoo
from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.observability import moe as obs_moe
from deeplearning4j_tpu.observability import opindex
from deeplearning4j_tpu.ops import sparse_attention as sa
from tests.test_sdar_moe import _count

RTOL = 2e-5
VOCAB, L, D, TOPK = 64, 32, 32, 8
SMALL = dict(n_layers=2, n_experts=16, experts_held=4, first_expert=4,
             vocab_size=VOCAB, hidden=D, n_heads=4, n_kv_heads=2, head_dim=8,
             expert_width=24, experts_per_token=3, index_heads=2,
             index_head_dim=8, index_topk=TOPK, dtype=zoo.F32)
HOW = dict(top_k=3, first_expert=4, head_dim=8, index_heads=2,
           index_head_dim=8, topk=TOPK)
ATTENTION = ["attn_ln_g", "Wq", "Wk", "Wv", "Wo", "q_norm_g", "k_norm_g"]
INDEXER = list(ref.INDEXER_LEAVES)
EXPERTS = ["ln_g", "Wr", "Wg", "Wu", "Wd"]
SCOPES = ("attn", "dsa_indexer", "dsa_select", "sparse_attention", "dsa_kl",
          "route", "experts")


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= rtol * max(np.abs(b).max(), 1e-30), (
        np.abs(a - b).max(), np.abs(b).max())


def _make(seed=1, **more):
    return zoo.keye_vl2_moe(seed=seed, **{**SMALL, **more})


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


def _selections(net, x, params=None):
    """The selection every sparse layer makes in the net's train-mode
    forward, {layer: words [b, L/32, L]}, handed back in the layers' new
    state, as the benchmark's check takes it."""
    layers = [layer for layer in net.layers
              if hasattr(layer, "hands_back_selection")]
    for layer in layers:
        layer.hands_back_selection = True
    try:
        _, state = jax.jit(lambda p, s, x: net._forward(
            p, s, x, train=True, rng=None))(
                net.params if params is None else params, net.state,
                jnp.asarray(x))
    finally:
        for layer in layers:
            layer.hands_back_selection = False
    return {layer.name: state[layer.name]["selection"] for layer in layers}


def _following(net, x):
    """The net's state with the program's selections in it."""
    return {name: {**s, "selection": sel} for name, s in net.state.items()
            for sel in [_selections(net, x).get(name)] if sel is not None}


# ------------------------------------------------------------------ the net
def test_the_layers_and_the_published_defaults():
    from deeplearning4j_tpu.nn.conf import layers_decoder as conf
    net = _make()
    kinds = [type(layer) for layer in net.conf.layers]
    assert kinds == [conf.TokenEmbedding, conf.SparseMoeBlock,
                     conf.SparseMoeBlock, conf.RmsNorm, conf.TokenOutput]
    assert net.conf.layers[-1].causal is True
    assert net.conf.layers[-1].tied_to is None
    assert [ref.kind_of(net.params[f"layer_{i}"]) for i in range(5)
            if f"layer_{i}" in net.params] == [
        None, "sparse_experts", "sparse_experts", None, None]
    assert sorted(net.params["layer_1"]) == sorted(ATTENTION + INDEXER
                                                   + EXPERTS)
    d = conf.SparseMoeBlock()
    assert (d.index_heads, d.index_head_dim, d.index_topk) == (4, 16, 16)
    import inspect
    defaults = {k: v.default for k, v in inspect.signature(
        zoo.keye_vl2_moe).parameters.items()}
    assert defaults["index_topk"] == 2048 and defaults["rope_theta"] == 1e7
    assert defaults["index_heads"] == 16 and defaults["index_head_dim"] == 64


def test_logits_match_the_reference(net, batch):
    x = jnp.asarray(batch.features)
    got = net.output(batch.features)
    assert got.shape == (2, L, VOCAB) and got.dtype == jnp.float32
    # the reference's own selection at float32 is the program's
    want = jax.jit(lambda p, s, x: ref.logits(p, s, x, **HOW))(
        net.params, net.state, x)
    _close(got, want)
    following = jax.jit(lambda p, s, x: ref.logits(p, s, x, **HOW))(
        net.params, _following(net, x), x)
    _close(got, following)


def test_the_losses_match_the_reference_apart_and_summed(net, batch):
    x, y = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    total, state = jax.jit(lambda p, s: net._loss(p, s, x, y, None, None,
                                                  None))(net.params, net.state)
    index_loss = sum(float(state[n]["dsa_indexer_kl"])
                     for n in ("layer_1", "layer_2"))
    want, (_, _, parts) = jax.jit(lambda p, s: ref.loss(
        p, s, x, y, with_logits=True, **HOW))(net.params, net.state)
    ce, want_index = (float(v) for v in parts)
    assert abs(float(total) - float(want)) <= 1e-5 * float(want)
    assert abs(index_loss - want_index) <= 1e-4 * want_index
    assert abs(float(total) - index_loss - ce) <= 1e-5 * ce
    assert 3.5 < ce < 5.0                        # about log(64) at init
    assert 0.0 < want_index < 2.0
    assert abs(net.score(batch) - float(total)) <= 1e-6 * float(total)


@pytest.fixture(scope="module")
def gradients(net, batch):
    x, y = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    got = jax.jit(jax.grad(lambda p: net._loss(
        p, net.state, x, y, None, None, None)[0]))(net.params)
    want = jax.jit(jax.grad(lambda p: ref.loss(
        p, net.state, x, y, **HOW)))(net.params)
    return got, want


GROUPS = [(f"layer_{i}", leaf) for i, leaves in {
    0: ["W"], 1: ATTENTION + INDEXER + EXPERTS,
    2: ATTENTION + INDEXER + EXPERTS, 3: ["g"], 4: ["W"]}.items()
    for leaf in leaves]


@pytest.mark.parametrize("layer,name", GROUPS,
                         ids=[f"{l}.{n}" for l, n in GROUPS])
def test_gradient_of_every_parameter_group(net, gradients, layer, name):
    got, want = gradients
    assert sorted(got[layer]) == sorted(net.params[layer])
    assert float(jnp.abs(want[layer][name]).max()) > 0
    _close(got[layer][name], want[layer][name], rtol=1e-4)


def test_each_loss_reaches_its_own_leaves_alone(net, batch):
    """The indexer's loss moves the indexer's five leaves and no other;
    the layer's output moves every leaf but those five."""
    layer, p = net.layers[1], net.params["layer_1"]
    x = net.feed_forward(batch.features, train=True)[0]
    cot = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)
    state = net.state["layer_1"]
    of_kl = jax.jit(jax.grad(lambda p: layer.apply(
        p, state, x, train=True)[1]["dsa_indexer_kl"]))(p)
    of_out = jax.jit(jax.grad(lambda p: jnp.sum(
        layer.apply(p, state, x, train=True)[0] * cot)))(p)
    for leaf in p:
        by_kl = np.abs(np.asarray(of_kl[leaf])).max()
        by_out = np.abs(np.asarray(of_out[leaf])).max()
        if leaf in INDEXER:
            assert by_kl > 0 and by_out == 0, leaf
        else:
            assert by_kl == 0 and by_out > 0, leaf


def test_indexer_leaves_that_keep_the_selection_leave_the_data_loss(
        net, batch):
    """Doubling ``W_w`` doubles every score, exactly: the same keys are
    kept, the logits (and so the cross-entropy) are the same to the bit,
    the indexer's loss is not."""
    x, y = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    twice = {**net.params, "layer_1": {
        **net.params["layer_1"], "W_w": 2 * net.params["layer_1"]["W_w"]}}
    loss = jax.jit(lambda p: net._loss(p, net.state, x, y, None, None, None))
    forward = jax.jit(lambda p: net._forward(p, net.state, x, train=True,
                                             rng=None)[0])
    kl = [float(loss(p)[1]["layer_1"]["dsa_indexer_kl"])
          for p in (net.params, twice)]
    assert kl[0] != kl[1]
    np.testing.assert_array_equal(forward(net.params), forward(twice))
    np.testing.assert_array_equal(_selections(net, x)["layer_1"],
                                  _selections(net, x, twice)["layer_1"])


# ------------------------------------------------------------ the selection
def test_the_selection_is_the_references_at_float32(net, batch):
    x = jnp.asarray(batch.features)
    acts = net.feed_forward(x, train=True)
    for i in (1, 2):
        p = net.params[f"layer_{i}"]
        words = np.asarray(_selections(net, x)[f"layer_{i}"])
        keep = np.asarray(sa.unpack(jnp.asarray(words)))
        assert (keep.sum(-1) == np.minimum(np.arange(L) + 1, TOPK)).all()
        assert not np.triu(keep, 1).any()
        for row in range(2):
            h = ref.rms_norm(acts[i - 1][row], p["attn_ln_g"])
            want = ref.select(*ref.indexer(p, h, index_heads=2,
                                           index_head_dim=8), TOPK)
            np.testing.assert_array_equal(words[row], want)
            held = ref.check_selection(p, acts[i - 1][row], words[row],
                                       **HOW)
            assert np.asarray(held["rows_count_ok"]).all()
            assert float(np.max(held["worst"])) <= 0.0
            assert int(np.sum(held["swaps"])) == 0


def test_the_check_refuses_a_selection_at_random_or_by_position(net, batch):
    acts = net.feed_forward(batch.features, train=True)
    p, x = net.params["layer_1"], acts[0][0]
    rng = np.random.default_rng(0)
    rows = np.arange(L)[:, None]
    at_random = np.zeros((1, L, L), bool)
    for t in range(L):
        at_random[0, t, rng.choice(t + 1, min(t + 1, TOPK),
                                   replace=False)] = True
    recent = (rows >= np.arange(L)[None]) & (rows - np.arange(L)[None]
                                              < TOPK)
    for keep in (at_random, recent[None]):
        words = sa.pack(jnp.asarray(keep))[0]
        held = ref.check_selection(p, x, words, **HOW)
        assert np.asarray(held["rows_count_ok"]).all()
        assert float(np.max(held["worst"])) > 1.0
    # one key too many in one row is refused too
    right = np.asarray(_selections(net, batch.features)["layer_1"][0])
    keep = np.array(sa.unpack(jnp.asarray(right[None])))[0]
    keep[20, np.flatnonzero(~keep[20, :21])[0]] = True
    held = ref.check_selection(p, x, sa.pack(jnp.asarray(keep[None]))[0],
                               **HOW)
    assert not np.asarray(held["rows_count_ok"])[20]


def test_pack_and_unpack_are_inverse_and_count_the_pairs():
    keep = jax.random.bernoulli(jax.random.PRNGKey(0), 0.3, (2, 64, 64))
    words = sa.pack(keep)
    assert words.shape == (2, 2, 64) and words.dtype == jnp.int32
    np.testing.assert_array_equal(sa.unpack(words), keep)
    assert int(sa.selected_pairs(words)) == int(keep.sum())
    # bit j of word i of row t is key j * L/32 + i
    one = jnp.zeros((1, 64, 64), bool).at[0, 5, 33].set(True)
    assert int(sa.pack(one)[0, 1, 5]) == 1 << 16


def _index_operands(length, seed=0, ties=False, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    qI = jax.random.normal(ks[0], (2, length, 2, 64), dtype)
    kI = jax.random.normal(ks[1], (2, length, 64), dtype)
    if ties:
        # keys in pairs of equal rows: every score comes twice
        kI = jnp.repeat(kI[:, ::2], 2, axis=1)
    w = jax.random.normal(ks[2], (2, length, 2), jnp.float32) * 0.1
    return qI, kI, w


def _brute_selection(qI, kI, w, topk):
    """The rule in numpy: highest score first, the lower key among
    equals."""
    scores = np.asarray(sa.index_scores(qI, kI, w))
    b, length, _ = scores.shape
    keep = np.zeros(scores.shape, bool)
    for i in range(b):
        for t in range(length):
            order = sorted(range(t + 1), key=lambda s: (-scores[i, t, s], s))
            keep[i, t, order[:topk]] = True
    return keep


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_dsa_select_takes_the_top_k_and_the_lower_key_among_equals(
        monkeypatch, backend, ties):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    qI, kI, w = _index_operands(256, ties=ties)
    topk = 48
    assert sa.dsa_select_supported(qI, kI, w, topk)
    fn = sa.dsa_select_xla if backend == "xla" else sa.dsa_select_pallas
    sel, lse = jax.jit(lambda *a: fn(*a, topk=topk))(qI, kI, w)
    keep = np.asarray(sa.unpack(sel))
    np.testing.assert_array_equal(keep, _brute_selection(qI, kI, w, topk))
    scores = np.asarray(sa.index_scores(qI, kI, w), np.float64)
    want = np.log(np.sum(np.where(keep, np.exp(scores), 0.0), axis=-1))
    _close(lse, want, rtol=1e-6)
    if ties:
        # a tie straddles the k-th place in many rows
        assert (np.sort(scores, axis=-1) == np.roll(np.sort(
            scores, axis=-1), 1, axis=-1)).any()


def _attention_operands(length, hq, hkv, dh, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (2, length, hq, dh), jnp.float32),
            jax.random.normal(ks[1], (2, length, hkv, dh), jnp.float32),
            jax.random.normal(ks[2], (2, length, hkv, dh), jnp.float32))


def _window(length, width):
    rows = np.arange(length)[:, None]
    keys = np.arange(length)[None]
    keep = (keys <= rows) & (rows - keys < width)
    return sa.pack(jnp.asarray(np.stack([keep, keep])))


@pytest.mark.parametrize("how", ["indexer", "window"])
def test_sparse_attention_kernels_are_the_dense_masked_softmax(
        monkeypatch, how):
    """Forward, the log-sum-exp and every gradient of the tiled kernels
    against the XLA form. A window of 64 keys leaves 3 of the 12 causal
    tile pairs of each sequence without a kept key, and the walk skips
    them."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    length = 1024
    q, k, v = _attention_operands(length, 8, 1, 64)
    if how == "window":
        sel = _window(length, 64)
    else:
        sel = sa.dsa_select_xla(*_index_operands(length, 2), topk=128)[0]
    assert sa.sparse_attention_supported(q, k, v, sel)
    cot = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)

    def run(fn):
        def loss(q, k, v):
            o, lse, tiles = fn(q, k, v, sel)
            return jnp.sum(o * cot), (o, lse, tiles)
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
            q, k, v)

    (_, (o_x, lse_x, tiles_x)), g_x = run(sa.sparse_attention_xla)
    (_, (o_p, lse_p, tiles_p)), g_p = run(sa.sparse_attention_pallas)
    _close(o_p, o_x)
    _close(lse_p, lse_x)
    for a, b in zip(g_p, g_x):
        _close(a, b, rtol=1e-4)
    # the XLA form walks a sequence as one tile
    assert [int(n) for n in tiles_x] == [2, 0]
    walked, skipped = (int(n) for n in tiles_p)
    assert walked + skipped == 2 * 12
    if how == "window":
        assert skipped == 2 * 3
    else:
        assert skipped == 0


def test_sparse_attention_keeping_every_key_is_the_causal_kernel(
        monkeypatch):
    """Where every row keeps every earlier key (no more than ``topk`` of
    them: the first 2,048 rows of the cell), the kernels under the
    selection are the causal kernels of ops/attention.py to the bit, in
    bf16 as on the chip: the same walk, the same tile bodies, forward and
    every gradient. So what the selection adds is the mask alone."""
    from deeplearning4j_tpu.ops import attention as att
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    length = 512
    q, k, v = (a.astype(jnp.bfloat16)
               for a in _attention_operands(length, 8, 1, 64))
    causal = np.tril(np.ones((length, length), bool))
    sel = sa.pack(jnp.asarray(np.stack([causal, causal])))
    cot = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * cot),
            (0, 1, 2)))(q, k, v)

    got, g_got = run(lambda q, k, v: sa.sparse_attention_pallas(
        q, k, v, sel)[0])
    want, g_want = run(att.causal_attention_pallas)
    assert float(got) == float(want)
    for a, b in zip(g_got, g_want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_the_indexer_loss_kernel_is_the_xla_form(monkeypatch):
    """The loss and its gradient into qI, kI and w, made in one pass in
    the forward, against autodiff of the dense form."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    length = 512
    q, k, _ = _attention_operands(length, 4, 2, 64)
    qI, kI, w = _index_operands(length, 3)
    sel, lse_index = sa.dsa_select_xla(qI, kI, w, topk=96)
    _, lse, _ = sa.sparse_attention_xla(q, k, k, sel)
    assert sa.dsa_kl_supported(q, k, qI, sel)

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda a, b, c: fn(q, k, lse, a, b, c, sel, lse_index),
            (0, 1, 2)))(qI, kI, w)

    want, g_want = run(sa.dsa_indexer_loss_xla)
    got, g_got = run(sa.dsa_indexer_loss_pallas)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    for a, b in zip(g_got, g_want):
        _close(a, b, rtol=1e-4)


def test_the_layer_on_the_kernels_is_the_layer_on_xla(monkeypatch):
    """A whole layer at a length the kernels take, both registries'
    ways: the same selection, output and indexer loss."""
    from deeplearning4j_tpu.ops import registry
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    net = _make(4, n_layers=1, head_dim=64, index_head_dim=64,
                index_topk=64)
    layer, p, s = net.layers[1], net.params["layer_1"], net.state["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 256, D), jnp.float32)
    outs = {}
    for backend in ("pallas", "xla"):
        with registry.use_backend(backend, "xla"):
            outs[backend] = jax.jit(lambda p, x: layer.apply(
                p, s, x, train=True))(p, x)
    (y_p, s_p), (y_x, s_x) = outs["pallas"], outs["xla"]
    _close(y_p, y_x)
    _close(s_p["dsa_indexer_kl"], s_x["dsa_indexer_kl"], rtol=1e-5)
    assert int(s_p["dsa_selected_pairs"]) == int(s_x["dsa_selected_pairs"]) \
        == sum(min(t + 1, 64) for t in range(256))


# ----------------------------------------------------------- the shares
def test_the_8_shares_add_up_to_the_uncut_layer():
    """What the 8 chips of a deployment add to a row, each its own two
    experts of 16, with the attention (and the indexer) that every chip
    computes alike counted once, sums to what the uncut reference layer
    gives."""
    whole = _make(5, n_layers=1, experts_held=16, first_expert=0)
    p = whole.params["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, L, D), jnp.float32)
    how = {**HOW, "first_expert": 0}
    uncut = jax.jit(lambda x: ref.block("sparse_experts", p, {}, x,
                                        **how)[0])(x[0])
    a = jax.jit(lambda x: ref.attention(p, x, **{
        k: v for k, v in how.items() if k not in ("top_k", "first_expert")
    })[0])(x[0])

    def share(first):
        part = _make(5, n_layers=1, experts_held=2, first_expert=first)
        q = dict(p)
        for name in ("Wg", "Wu", "Wd"):
            q[name] = p[name][first:first + 2]
        return jax.jit(lambda q, x: part.layers[1].apply(
            q, part.state["layer_1"], x)[0])(q, x)[0]

    added = sum(np.asarray(share(first)) - a for first in range(0, 16, 2))
    _close(a + added, uncut)


# ------------------------------------------------------------ the net path
def test_fit_with_default_arguments_lowers_both_losses(net):
    ring = [_batch(seed) for seed in (4, 5)]
    assert ring[0].features.dtype == ring[0].labels.dtype == np.int32
    before = [net.score(ds) for ds in ring]
    net.fit(ListDataSetIterator(ring * 4))
    assert all(net.score(ds) < b for ds, b in zip(ring, before))
    read = obs_moe.sparse_attention(net)
    assert sorted(read) == ["layer_1", "layer_2"]
    assert read["layer_1"]["pairs"] == 2 * sum(min(t + 1, TOPK)
                                               for t in range(L))
    assert read["layer_1"]["kl"] > 0
    # the XLA form walks a sequence's scores as one tile
    assert (read["layer_1"]["walked"], read["layer_1"]["skipped"]) == (2, 0)


def test_only_a_forward_traced_to_hand_it_back_holds_the_selection(
        net, batch):
    """The training step's state has no ``selection``; a forward traced
    with ``hands_back_selection`` set has one a sparse layer, the words
    of the keys its attention read."""
    x, y = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    _, state = jax.jit(lambda p, s: net._loss(p, s, x, y, None, None, None))(
        net.params, net.state)
    assert not any("selection" in s for s in state.values())
    assert not any("selection" in s for s in net.state.values())
    words = _selections(net, x)
    assert sorted(words) == ["layer_1", "layer_2"]
    assert all(w.shape == (2, 1, L) and w.dtype == jnp.int32
               for w in words.values())
    assert not net.layers[1].hands_back_selection


def test_configuration_round_trips_through_json(net):
    from deeplearning4j_tpu.nn.conf.core import MultiLayerConfiguration
    again = MultiLayerConfiguration.from_json(net.conf.to_json())
    assert again.layers == net.conf.layers
    assert again.layers[1].index_topk == TOPK
    assert again.layers[1].rope_theta == 1e7


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
        assert ("forward", scope) in seen, scope
    # the selection has no gradient; everything else has a backward
    for scope in set(SCOPES) - {"dsa_select"}:
        assert ("backward", scope) in seen, scope


def test_trace_time_counters_and_the_collector(batch):
    from deeplearning4j_tpu.observability.metrics import get_registry
    select = _count("dl4j_dsa_select_calls_total", backend="xla")
    attention = _count("dl4j_sparse_attention_calls_total",
                       direction="forward", backend="xla")
    fresh = _make(3)
    fresh.fit(ListDataSetIterator([batch]))
    assert _count("dl4j_dsa_select_calls_total", backend="xla") >= select + 2
    assert _count("dl4j_sparse_attention_calls_total", direction="forward",
                  backend="xla") >= attention + 2
    read = obs_moe.sparse_attention(fresh)
    samples = {(family.name, s.labels.get("layer"), s.labels.get("kind")):
               s.value for family in get_registry().collect()
               for s in family.samples if family.name in (
                   "dl4j_dsa_indexer_kl", "dl4j_dsa_selected_pairs",
                   "dl4j_sparse_attention_tiles")}
    for name in ("layer_1", "layer_2"):
        assert samples[("dl4j_dsa_indexer_kl", name, None)] == pytest.approx(
            read[name]["kl"])
        assert samples[("dl4j_dsa_selected_pairs", name, None)] == read[
            name]["pairs"] > 0
        for kind in ("walked", "skipped"):
            assert ("dl4j_sparse_attention_tiles", name, kind) in samples
