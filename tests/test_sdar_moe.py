"""The decoder of zoo.sdar_moe on the net's own path, against the plain
reference the benchmark holds it to (benchmark/reference/sdar_moe.py),
at small widths on the CPU with seeded weights.

Tolerances: the float32 policy runs the same mathematics as the
reference in another order (grouped products by sorted chunks, attention
by tiles), so the two agree to float32 rounding of sums of tens to
hundreds of terms: 2e-5 relative to the largest entry compared. The
suite runs with x64 on; every array here is float32 by construction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe as ref
from deeplearning4j_tpu import (
    MultiLayerNetwork, NeuralNetConfiguration, zoo)
from deeplearning4j_tpu.datasets import (
    BlockDiffusionPreProcessor, DataSet, DevicePrefetchIterator,
    ListDataSetIterator, PreProcessingIterator)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.decoder import rows_total
from deeplearning4j_tpu.nn.conf.layers_decoder import (
    RoutedExperts, TokenEmbedding, TokenOutput)
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.observability import opindex
from deeplearning4j_tpu.observability.metrics import get_registry
from deeplearning4j_tpu.observability.trace import Tracer, set_tracer
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import grouped
from deeplearning4j_tpu.ops import registry

RTOL = 2e-5
VOCAB, L, BLOCK = 64, 16, 4
SMALL = dict(n_layers=2, n_experts=16, experts_held=4, first_expert=4,
             vocab_size=VOCAB, hidden=32, n_heads=4, n_kv_heads=2,
             head_dim=16, expert_width=24, experts_per_token=4,
             dtype=zoo.F32)
HOW = dict(top_k=4, first_expert=4)


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= rtol * max(np.abs(b).max(), 1e-30), (
        np.abs(a - b).max(), np.abs(b).max())


def _untied(net, seed):
    """The zoo starts every share's router columns as copies of the first
    share's; a router seeded column by column (unequal weights among a
    row's experts, pairs here that follow the row) is the general case
    the comparisons below are made on. Every token that carries loss is
    ``[MASK]``, one embedding: the fixture's seed is one at which those
    rows take an expert held here in the last layer, whose experts have
    no gradient otherwise."""
    for i, (name, p) in enumerate(net.params.items()):
        if "Wr" in p:
            p["Wr"] = 0.5 * jax.random.normal(
                jax.random.PRNGKey(seed + i), p["Wr"].shape, p["Wr"].dtype)
    return net


@pytest.fixture(scope="module")
def net():
    return _untied(zoo.sdar_moe(seed=1, learning_rate=3e-3, **SMALL), 15)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    return BlockDiffusionPreProcessor(BLOCK, VOCAB - 1, seed=3).pre_process(
        DataSet(rng.integers(0, VOCAB - 1, (2, L))))


# ------------------------------------------------- system against reference
def test_logits_match_the_reference(net, batch):
    out = net.output(batch.features)
    assert out.shape == (2, L, VOCAB) and out.dtype == jnp.float32
    _close(out, ref.logits(net.params, net.state,
                           jnp.asarray(batch.features), **HOW))


def test_loss_matches_the_reference(net, batch):
    want = ref.loss(net.params, net.state, *map(jnp.asarray, (
        batch.features, batch.labels, batch.labels_mask)), **HOW)
    assert abs(net.score(batch) - float(want)) <= RTOL * float(want)


def _gradients(net, batch):
    got = jax.grad(lambda p: net._loss(
        p, net.state, *net._batch_args(batch), rng=None)[0])(net.params)
    want = jax.grad(lambda p: ref.loss(p, net.state, *map(jnp.asarray, (
        batch.features, batch.labels, batch.labels_mask)), **HOW))(net.params)
    return got, want


GROUPS = [("layer_0", "W"), ("layer_4", "W"), ("layer_3", "g")] + [
    (layer, name) for layer in ("layer_1", "layer_2") for name in (
        "Wq", "Wk", "Wv", "Wo", "q_norm_g", "k_norm_g", "attn_ln_g",
        "ln_g", "Wr", "Wg", "Wu", "Wd")]


@pytest.fixture(scope="module")
def gradients(net, batch):
    return _gradients(net, batch)


@pytest.mark.parametrize("layer,name", GROUPS,
                         ids=[f"{a}.{b}" for a, b in GROUPS])
def test_gradient_of_every_parameter_group(gradients, layer, name):
    """1e-4: a gradient is a sum over 32 rows of products of five or six
    float32 factors, summed in another order on each side."""
    got, want = gradients
    assert np.abs(np.asarray(want[layer][name])).max() > 0
    _close(got[layer][name], want[layer][name], rtol=1e-4)


# ------------------------------------------------------------ the mask rule
def _table(seq, block):
    """M(i, j) of ISSUE 31, entry by entry."""
    table = np.zeros((2 * seq, 2 * seq), bool)
    for i in range(2 * seq):
        for j in range(2 * seq):
            bi, bj = (i % seq) // block, (j % seq) // block
            if i < seq and j < seq:
                table[i, j] = bi == bj
            elif i < seq:
                table[i, j] = bj < bi
            elif j >= seq:
                table[i, j] = bj <= bi
    return table


@pytest.mark.parametrize("seq,block", [(20, 4), (12, 3), (8, 8), (130, 2)])
def test_mask_rule_matches_a_brute_force_table(seq, block):
    rows = jnp.arange(2 * seq, dtype=jnp.int32)
    got = att.block_diffusion_visible(rows[:, None], rows[None, :], seq, block)
    want = _table(seq, block)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(ref.visible(
        rows[:, None], rows[None, :], seq, block)), want)
    # every row sees itself; visible pairs are L * B + L^2
    assert want.diagonal().all() and want.sum() == seq * block + seq * seq


@pytest.mark.parametrize("seq,bq,bk", [(256, 128, 128), (256, 128, 256),
                                       (512, 128, 512), (384, 128, 128),
                                       (512, 512, 512), (512, 256, 512)])
def test_live_tiles_are_those_with_a_visible_entry(seq, bq, bk):
    table = _table(seq, 4)
    want = {(qi, ki) for qi in range(2 * seq // bq)
            for ki in range(2 * seq // bk)
            if table[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].any()}
    assert {(int(q), int(k)) for q, k, _ in
            att._bd_live_tiles(seq, 4, bq, bk)} == want


def _dense_attention(q, k, v, seq, block):
    """Masked softmax over the whole [2L, 2L] table, float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, group, axis=2), np.repeat(v, group, axis=2)
    s = np.einsum("bihd,bjhd->bhij", q, k) / np.sqrt(q.shape[-1])
    s = np.where(_table(seq, block)[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhij,bjhd->bihd", p / p.sum(-1, keepdims=True), v)


def _qkv(seq, heads, kv_heads, dh, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h: (1, 2 * seq, h, dh)
    return [jax.random.normal(k, shape(h), jnp.float32).astype(dtype)
            for k, h in zip(ks, (heads, kv_heads, kv_heads, heads))]


# heads, key/value heads, operand dtype, tolerance. 8 on 1 is the SDAR
# cell's group (32 on 4); bf16: the products' operands, p among them,
# and the outputs are rounded to 8 bits
F32_4ON2 = (4, 2, jnp.float32, 2e-5)


@pytest.mark.parametrize("backend,seq,block,heads,kv_heads,dtype,rtol", [
    ("xla", 20, 4, *F32_4ON2), ("xla", 12, 3, *F32_4ON2),
    ("pallas", 256, 4, *F32_4ON2), ("pallas", 128, 8, *F32_4ON2),
    ("pallas", 256, 4, 8, 1, jnp.float32, 2e-5),
    ("pallas", 128, 4, 8, 1, jnp.bfloat16, 2e-2),
    # a group of 1: the query tile is 512 positions, a whole half of the
    # rows, against key tiles of 512 of all three kinds
    ("pallas", 512, 4, 2, 2, jnp.float32, 2e-5),
    # the fused backward over two key/value heads (dK and dV zeroed at
    # each head's first step): a group of 16 (tiles of 128 rows against
    # 256 keys, so a key tile meets two query tiles and a clean query tile
    # two key tiles) and a group of 4 (tiles of 256 against 256)
    ("pallas", 256, 4, 32, 2, jnp.float32, 2e-5),
    ("pallas", 256, 4, 8, 2, jnp.float32, 2e-5)],
    ids=["xla-20-4", "xla-12-3", "pallas-256-4", "pallas-128-8",
         "pallas-256-4-8on1", "pallas-128-4-8on1-bf16", "pallas-512-4-1on1",
         "pallas-256-4-16on1x2", "pallas-256-4-4on1x2"])
def test_attention_matches_dense_masked_softmax(monkeypatch, backend, seq,
                                                block, heads, kv_heads,
                                                dtype, rtol):
    """Forward and the gradients of q, k, v. The Pallas kernels run in
    interpret mode; 2e-5: online softmax over tiles sums in another
    order."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    dh = 128 if backend == "pallas" else 16
    q, k, v, g = _qkv(seq, heads, kv_heads, dh, dtype=dtype)
    fn = registry.get("block_diffusion_mha", backend)
    if backend == "pallas":
        assert att.block_attention_supported(q, k, v, seq, block)
    out = fn(q, k, v, seq_len=seq, block_len=block)
    assert out.dtype == dtype
    _close(out, _dense_attention(q, k, v, seq, block), rtol)
    g = g.astype(jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(fn(*a, seq_len=seq, block_len=block)
                                      * g), (0, 1, 2))(q, k, v)

    def dense(q, k, v):     # the same table through jax, for autodiff
        group = q.shape[2] // k.shape[2]
        kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bihd,bjhd->bhij", q, kk) / np.sqrt(q.shape[-1])
        s = jnp.where(_table(seq, block)[None, None], s, -jnp.inf)
        return jnp.sum(jnp.einsum("bhij,bjhd->bihd",
                                  jax.nn.softmax(s, -1), vv) * g)

    want = jax.grad(dense, (0, 1, 2))(*(a.astype(jnp.float32)
                                        for a in (q, k, v)))
    for a, b in zip(got, want):
        assert a.dtype == dtype
        _close(a, b, rtol)


@pytest.mark.parametrize("seq,block,group", [(512, 4, 2), (1024, None, 4)],
                         ids=["block_diffusion", "causal"])
def test_forward_saves_the_log_sum_exp_of_the_masked_scores(monkeypatch, seq,
                                                            block, group):
    """The forward's second output feeds both backward kernels: for each
    row the log-sum-exp of its visible scaled scores, float32, laid
    [b * Hkv, G, rows]. Block-diffusion at L = 512 has key tiles of 512,
    so a noised row sees 4 of the 512 keys of its kind-0 tile and the
    running max starts from a tile that is masked but for those; causal
    at 1,024 rows carries max and sum over two key tiles."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    rows = 2 * seq if block else seq
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = 3.0 * jax.random.normal(ks[0], (1, rows, 2 * group, 128), jnp.float32)
    k, v = (jax.random.normal(key, (1, rows, 2, 128), jnp.float32)
            for key in ks[1:])
    if block:
        assert att._bd_key_tile(seq) == 512
        assert (0, 0, 0) in {tuple(int(x) for x in row) for row in
                             att._bd_live_tiles(seq, block, 128, 512)}
        visible = _table(seq, block)
    else:
        visible = np.tri(rows, dtype=bool)
    lse = jax.jit(lambda *a: att._bd_forward(*att._bd_split(*a), seq,
                                             block)[1])(q, k, v)
    assert lse.shape == (2, group, rows) and lse.dtype == jnp.float32
    q64, k64 = (np.asarray(a, np.float64) for a in (q, k))
    s = np.einsum("ikgd,jkd->kgij", q64[0].reshape(rows, 2, group, 128),
                  k64[0]) / np.sqrt(128)
    s = np.where(visible, s, -np.inf)
    top = s.max(-1, keepdims=True)
    want = (top + np.log(np.exp(s - top).sum(-1, keepdims=True)))[..., 0]
    np.testing.assert_allclose(np.asarray(lse), want, rtol=0, atol=2e-5)


def test_unsupported_shapes_fall_back_to_xla(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    q, k, v, _ = _qkv(20, 4, 2, 16)
    assert not att.block_attention_supported(q, k, v, 20, 4)
    _close(att.block_diffusion_mha_pallas(q, k, v, seq_len=20, block_len=4),
           _dense_attention(q, k, v, 20, 4))


# --------------------------------------------------------- the expert layer
D, F, EXPERTS, TOP = 32, 24, 16, 4


WIDE = 128      # a width the grouped kernels take (ops/grouped.py)


def _expert_net(held, first, seed=5, d=D, f=F):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .dtype(zoo.F32)
            .weight_init({"type": "normal", "mean": 0.0, "std": 0.3}).list()
            .layer(RoutedExperts(n_out=d, n_experts=EXPERTS,
                                 experts_per_token=TOP, expert_width=f,
                                 experts_held=held, first_expert=first))
            .layer(TokenOutput(n_out=8))
            .set_input_type(InputType.recurrent(d)).build())
    return MultiLayerNetwork(conf).init()


def _share(whole, held, first):
    """The net holding ``held`` experts from ``first`` on, with the
    weights ``whole`` (a net holding all of them) has for them."""
    d, f = whole.params["layer_0"]["Wg"].shape[1:]
    part = _expert_net(held, first, d=d, f=f)
    p = dict(whole.params["layer_0"])
    for name in ("Wg", "Wu", "Wd"):
        p[name] = p[name][first:first + held]
    part.params = {**part.params, "layer_0": p}
    return part


def test_the_shares_add_up_to_the_uncut_layer():
    """What the 8 chips of a deployment add to a row, each its own 2 of
    16 experts, sums to what the uncut reference layer adds."""
    whole = _expert_net(EXPERTS, 0)
    a = jax.random.normal(jax.random.PRNGKey(2), (1, 24, D), jnp.float32)
    p = whole.params["layer_0"]
    uncut = ref.experts(p, a[0], top_k=TOP)[0]
    added = sum(
        np.asarray(_share(whole, 2, first).feed_forward(a)[0][0]) - a[0]
        for first in range(0, EXPERTS, 2))
    _close(added, uncut - a[0])
    # and one share is what the reference gives for that share
    part = _share(whole, 2, 6)
    want = ref.experts(part.params["layer_0"], a[0], top_k=TOP,
                       first_expert=6)[0]
    _close(part.feed_forward(a)[0][0], want)


@pytest.mark.parametrize("path,d,f", [("xla_chunks", D, F),
                                      ("pallas", WIDE, WIDE)])
def test_a_biased_router_drops_and_pads_nothing(monkeypatch, path, d, f):
    """Three times the expected pairs land here, on experts of unequal
    load spanning several chunks (of the loop) or two blocks (of the
    kernels, in interpret mode), and the result is still the
    reference's, pair for pair."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    held, first, rows = 4, 8, 600
    part = _share(_expert_net(EXPERTS, 0, d=d, f=f), held, first)
    p = dict(part.params["layer_0"])
    ran = _count("dl4j_moe_grouped_matmul_calls_total", backend=path)
    # rows with a common positive part, and router columns of the held
    # experts that like it
    a = 0.5 + jax.random.normal(jax.random.PRNGKey(3), (1, rows, d),
                                jnp.float32)
    p["Wr"] = p["Wr"].at[:, first:first + held].add(
        jnp.asarray([0.6, 0.5, 0.4, 0.3], jnp.float32)[None, :])
    part.params = {**part.params, "layer_0": p}
    acts, state = part._forward(part.params, part.state,
                                a.astype(jnp.float32), train=False, rng=None,
                                to_layer=1)
    counts = np.asarray(state["layer_0"]["expert_rows"])
    expected = rows * TOP * held / EXPERTS
    assert counts.sum() >= 3 * expected and counts.max() > 128 * 2
    assert _count("dl4j_moe_grouped_matmul_calls_total",
                  backend=path) == ran + 1
    if path == "pallas":        # a block is 4 experts x 256 pairs
        assert counts.sum() > 4 * 256
    want = ref.experts(p, a[0], top_k=TOP, first_expert=first)[0]
    _close(acts[0], want)
    # the counts are the reference's routing too
    c, _ = ref.routing(ref.rms_norm(a[0], p["ln_g"]), p, TOP, first)
    np.testing.assert_array_equal(counts, np.asarray((c > 0).sum(axis=0)))


def _pairs(counts, elsewhere, rows, d, f, seed=0):
    """Sorted pairs with these counts (an expert takes a row once) and
    ``elsewhere`` trailing pairs of experts held on other chips."""
    rng = np.random.default_rng(seed)
    n = len(counts)
    idx = np.concatenate([rng.permutation(rows)[:c] for c in counts]
                         + [rng.integers(0, rows, elsewhere)])
    coef = rng.uniform(0.1, 1.0, len(idx))
    x = rng.normal(size=(rows, d))
    wg, wu = (0.1 * rng.normal(size=(n, d, f)) for _ in range(2))
    wd = 0.1 * rng.normal(size=(n, f, d))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (f32(x), jnp.asarray(idx, jnp.int32), f32(coef),
            jnp.asarray(counts, jnp.int32), f32(wg), f32(wu), f32(wd))


def _dense_experts(x, rows, coef, counts, wg, wu, wd):
    """Expert by expert over its slice of the pairs, in jnp."""
    y, start = jnp.zeros(x.shape, jnp.float32), 0
    for e, c in enumerate(np.asarray(counts)):
        idx = rows[start:start + c]
        xs = x[idx]
        out = (jax.nn.silu(xs @ wg[e]) * (xs @ wu[e])) @ wd[e]
        y = y.at[idx].add(out * coef[start:start + c, None])
        start += c
    return y


# counts of 4 held experts, trailing pairs, the chunk the layer would pass
KERNEL_CASES = {
    "balanced": ((300, 300, 300, 300), 0, 512),
    "one_expert_takes_every_pair": ((0, 500, 0, 0), 0, 256),
    "an_expert_with_none": ((200, 0, 310, 90), 0, 256),
    # tiles are 256 rows: expert 1 ends its own tile part full, and
    # three experts together fill less than one tile's rows
    "counts_off_the_tile_two_experts": ((256, 300, 0, 0), 0, 256),
    "counts_off_the_tile_three_experts": ((5, 130, 1, 300), 0, 256),
    "no_pairs": ((0, 0, 0, 0), 64, 128),
    "past_one_block_of_pairs": ((400, 300, 200, 100), 0, 128),
    "one_expert_past_the_block": ((0, 500, 0, 0), 0, 64),
    "trailing_pairs_held_elsewhere": ((120, 260, 7, 50), 300, 256),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_expert_kernels_match_the_loop_and_a_dense_product(monkeypatch,
                                                           case):
    """The Pallas path (interpret mode) against the chunk loop and
    against a per-expert ``jnp`` product: forward and the gradients in
    ``x``, ``coef``, ``Wg``, ``Wu``, ``Wd``. 2e-5: each side sums its
    float32 products in another order."""
    counts, elsewhere, chunk = KERNEL_CASES[case]
    rows, d, f = 512, WIDE, 2 * WIDE
    args = _pairs(counts, elsewhere, rows, d, f)
    g = jax.random.normal(jax.random.PRNGKey(9), (rows, d), jnp.float32)

    def value_and_grads(fn):
        def loss(x, coef, wg, wu, wd):
            return jnp.sum(fn(x, args[1], coef, args[3], wg, wu, wd) * g)
        return jax.value_and_grad(loss, (0, 1, 2, 3, 4))(
            args[0], args[2], *args[4:])

    ffn = lambda *a: grouped.expert_ffn(*a, chunk=chunk)
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    assert grouped.grouped_supported(args[0], *args[4:], len(args[1]), chunk)
    block = grouped._block_pairs(len(args[1]), len(counts), chunk)
    # past one block the loop over the blocks runs a second trip
    assert int(grouped._n_blocks(args[3], block)) == (
        2 if case in ("past_one_block_of_pairs", "one_expert_past_the_block")
        else 0 if case == "no_pairs" else 1)
    got = value_and_grads(ffn)
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    assert not grouped.grouped_supported(args[0], *args[4:], len(args[1]),
                                         chunk)
    for want in (value_and_grads(ffn), value_and_grads(_dense_experts)):
        assert abs(got[0] - want[0]) <= RTOL * max(abs(want[0]), 1e-30)
        for a, b in zip(got[1], want[1]):
            _close(a, b)
    # pairs held elsewhere, and a sum of no pairs, weigh nothing
    assert not np.asarray(got[1][1])[sum(counts):].any()


def _widest(jaxpr):
    """The longest leading dimension among the arrays of two or more
    dimensions a program makes, sub-programs included."""
    widest = 0
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if len(shape) > 1:
                widest = max(widest, shape[0])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            widest = max(widest, _widest(sub))
    return widest


def test_expert_kernels_size_nothing_by_the_worst_case(monkeypatch):
    """``rows`` is as long as every row choosing only held experts (8
    times the expected load here); the kernels' gathers, products and
    scatter-adds are as long as a block and a tile an expert."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    rows, chunk, held = 2048, 256, 4
    args = _pairs((250, 250, 250, 250), rows * 4 - 1000, rows, WIDE, WIDE)
    assert len(args[1]) == 8 * held * chunk

    def loss(x, coef, wg, wu, wd):
        return jnp.sum(grouped.expert_ffn(x, args[1], coef, args[3], wg, wu,
                                          wd, chunk=chunk))

    program = jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3, 4)))(
        args[0], args[2], *args[4:])
    assert _widest(program.jaxpr) <= max(
        rows, held * chunk + held * grouped.TILE)


@pytest.mark.parametrize("why,d,f,interpret,backend", [
    ("a width of 64", 64, 128, True, "xla_chunks"),
    ("an expert width of 64", 128, 64, True, "xla_chunks"),
    ("a CPU without interpret mode", 128, 128, False, "xla_chunks"),
    ("whole lane tiles", 128, 256, True, "pallas")])
def test_expert_ffn_chooses_its_backend_from_the_call(monkeypatch, why, d, f,
                                                      interpret, backend):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1" if interpret else "0")
    args = _pairs((40, 30, 20, 10), 28, 64, d, f)
    assert grouped.grouped_supported(args[0], *args[4:], len(args[1]),
                                     128) == (backend == "pallas")
    ran = _count("dl4j_moe_grouped_matmul_calls_total", backend=backend)
    _close(grouped.expert_ffn(*args, chunk=128), _dense_experts(*args))
    assert _count("dl4j_moe_grouped_matmul_calls_total",
                  backend=backend) == ran + 1
    # one dtype for the rows and the weights, and one the MXU takes
    assert not grouped.grouped_supported(
        args[0].astype(jnp.bfloat16), *args[4:], len(args[1]), 128)


def test_expert_counts_ride_in_the_state(net, batch):
    _, state = net._forward(net.params, net.state,
                            jnp.asarray(batch.features), train=False,
                            rng=None)
    for name in ("layer_1", "layer_2"):
        rows = np.asarray(state[name]["expert_rows"])
        assert rows.dtype == np.int32 and rows.shape == (4,)
        np.testing.assert_array_equal(
            rows_total(np.asarray(state[name]["expert_rows_total"])),
            rows_total(np.asarray(net.state[name]["expert_rows_total"]))
            + rows)


# --------------------------------------------------------- the pre-processor
def test_preprocessor_shapes_dtypes_and_weights():
    rng = np.random.default_rng(1)
    x0 = rng.integers(0, 99, (3, 64))
    ds = BlockDiffusionPreProcessor(4, 99, seed=7).pre_process(DataSet(x0))
    assert ds.features.dtype == np.int32 and ds.features.shape == (3, 128)
    assert ds.labels.dtype == np.int32 and ds.labels_mask.dtype == np.float32
    np.testing.assert_array_equal(ds.features[:, 64:], x0)
    np.testing.assert_array_equal(ds.labels, x0)
    masked = ds.features[:, :64] == 99
    np.testing.assert_array_equal(ds.features[:, :64][~masked], x0[~masked])
    np.testing.assert_array_equal(ds.labels_mask > 0, masked)
    # one weight a block, 1/t with t in (0, 1]
    w = ds.labels_mask.reshape(3, 16, 4)
    for block in w.reshape(-1, 4):
        assert len(set(block[block > 0].tolist())) <= 1
    assert (w[w > 0] >= 1.0).all()


def test_preprocessor_masked_share_tracks_t():
    """Over 4,000 blocks of 8 the share of masked tokens among blocks of
    noise level t is t: binned by 1/weight, each bin of some 400 blocks
    is within 0.05. From t = 0.4 up, where all but 2% of the blocks have
    a masked token to read t from."""
    x0 = np.zeros((40, 800), np.int64)
    ds = BlockDiffusionPreProcessor(8, 1, seed=11).pre_process(DataSet(x0))
    w = ds.labels_mask.reshape(-1, 8)
    t = 1.0 / np.where(w.max(1) > 0, w.max(1), np.inf)     # 0 where unseen
    share = (w > 0).mean(1)
    seen = t > 0
    for lo in np.arange(0.4, 1.0, 0.1):
        pick = seen & (t >= lo) & (t < lo + 0.1)
        assert abs(share[pick].mean() - (lo + 0.05)) < 0.05


def test_preprocessor_same_seed_same_batch_and_reset():
    x0 = np.arange(64).reshape(2, 32) % 50
    a = BlockDiffusionPreProcessor(4, 63, seed=5)
    first, second = a.pre_process(DataSet(x0)), a.pre_process(DataSet(x0))
    again = BlockDiffusionPreProcessor(4, 63, seed=5).pre_process(DataSet(x0))
    np.testing.assert_array_equal(first.features, again.features)
    np.testing.assert_array_equal(first.labels_mask, again.labels_mask)
    assert (first.features != second.features).any()
    a.reset()
    np.testing.assert_array_equal(a.pre_process(DataSet(x0)).features,
                                  first.features)


@pytest.mark.parametrize("bad", [np.zeros((2, 30), np.int64),
                                 np.zeros((2, 32), np.float32)])
def test_preprocessor_refuses_what_it_cannot_noise(bad):
    with pytest.raises((TypeError, ValueError)):
        BlockDiffusionPreProcessor(4, 9).pre_process(DataSet(bad))


def test_preprocessor_records_a_host_span():
    tracer = Tracer()
    previous = set_tracer(tracer)
    try:
        BlockDiffusionPreProcessor(4, 9).pre_process(
            DataSet(np.zeros((1, 8), np.int64)))
    finally:
        set_tracer(previous)
    assert [s.name for s in tracer.spans()] == ["block_diffusion_noise"]


# ----------------------------------------------------- the fit loop, integers
def _ring(n, seed=0):
    rng = np.random.default_rng(seed)
    noise = BlockDiffusionPreProcessor(BLOCK, VOCAB - 1, seed=seed)
    return [noise.pre_process(DataSet(rng.integers(0, VOCAB - 1, (2, L))))
            for _ in range(n)]


def test_fit_scan_of_8_equals_eight_single_steps():
    """The chunked fit's contract (nn/multistep.py): the scan replays the
    per-batch loop, so parameters, counts and score agree. 1e-6: the
    scan body and the single step are compiled apart."""
    ring = _ring(8)
    one = _untied(zoo.sdar_moe(seed=2, learning_rate=1e-3, **SMALL), 12)
    eight = _untied(zoo.sdar_moe(seed=2, learning_rate=1e-3, **SMALL), 12)
    for ds in ring:
        one.fit_batch(ds)
    eight.fit(ListDataSetIterator(ring), multi_step=8, device_prefetch=True)
    assert eight.iteration == one.iteration == 8
    for a, b in zip(jax.tree_util.tree_leaves(one.params),
                    jax.tree_util.tree_leaves(eight.params)):
        _close(b, a, rtol=1e-6)
    np.testing.assert_array_equal(
        one.state["layer_1"]["expert_rows_total"],
        eight.state["layer_1"]["expert_rows_total"])
    assert abs(float(one.score_value) - float(eight.score_value)) < 1e-5


def test_fit_with_default_arguments_lowers_the_loss(net):
    ring = _ring(2, seed=4)
    before = [net.score(ds) for ds in ring]
    net.fit(PreProcessingIterator(ListDataSetIterator(
        [DataSet(ds.labels) for ds in ring]),
        BlockDiffusionPreProcessor(BLOCK, VOCAB - 1, seed=4)), epochs=20)
    assert all(net.score(ds) < b for ds, b in zip(ring, before))


def test_integer_ids_stay_integers_to_the_device(net, batch):
    x, y, fmask, lmask = net._batch_args(batch)
    assert jnp.issubdtype(x.dtype, jnp.integer)
    assert jnp.issubdtype(y.dtype, jnp.integer) and fmask is None
    assert lmask.dtype == jnp.float32
    (moved,) = list(DevicePrefetchIterator(ListDataSetIterator([batch])))
    assert isinstance(moved.features, jax.Array)
    assert jnp.issubdtype(moved.features.dtype, jnp.integer)
    assert jnp.issubdtype(moved.labels.dtype, jnp.integer)
    np.testing.assert_array_equal(net.output(moved.features),
                                  net.output(batch.features))


@pytest.mark.parametrize("what", ["features", "labels"])
def test_float_ids_are_refused_with_a_sentence(net, batch, what):
    bad = DataSet(batch.features, batch.labels, labels_mask=batch.labels_mask)
    setattr(bad, what, getattr(bad, what).astype(np.float32))
    with pytest.raises(TypeError, match="integer"):
        net.score(bad)


def test_streaming_raises_with_a_sentence(net, batch):
    with pytest.raises(NotImplementedError, match="no streaming path"):
        net.rnn_time_step(batch.features)


def test_configuration_round_trips_through_json(net):
    from deeplearning4j_tpu.nn.conf.core import MultiLayerConfiguration
    again = MultiLayerConfiguration.from_json(net.conf.to_json())
    assert again.layers == net.conf.layers


# ------------------------------------------------------------- observability
def test_token_embedding_standalone():
    conf = (NeuralNetConfiguration.builder().seed(1).dtype(zoo.F32).list()
            .layer(TokenEmbedding(n_out=8))
            .layer(TokenOutput(n_out=5, activation="identity"))
            .set_input_type(InputType.recurrent(11)).build())
    tiny = MultiLayerNetwork(conf).init()
    ids = np.asarray([[1, 2, 3, 10]])
    out = tiny.output(ids)
    w = np.asarray(tiny.params["layer_0"]["W"])
    _close(out[0], w[ids[0, :2]] @ np.asarray(tiny.params["layer_1"]["W"]))


@pytest.mark.parametrize("experts", ["xla_chunks", "pallas"])
def test_every_op_of_the_step_is_placed_under_a_scope(monkeypatch, net,
                                                      batch, experts):
    """The step's dots lie under attn, route or experts inside a layer,
    or in the head, and the op index places them all: with the experts'
    chunk loop, and with their kernels (interpret mode, a net of widths
    the kernels take), whose calls must not land outside a scope."""
    if experts == "pallas":
        monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
        net = _untied(zoo.sdar_moe(seed=1, **{
            **SMALL, "hidden": WIDE, "expert_width": WIDE}), 15)
    ran = _count("dl4j_moe_grouped_matmul_calls_total", backend=experts)
    step = jax.jit(net._step_fn())
    args = net._step_args(net._batch_args(batch), jax.random.PRNGKey(0))
    index = opindex.parse(step.lower(*args).compile().as_text())
    assert _count("dl4j_moe_grouped_matmul_calls_total",
                  backend=experts) > ran
    seen = set()
    for entry in index.values():
        phase, layer, _ = opindex.place(entry)
        # the interpreter's ``pl.when`` branches hold zero tiles that the
        # CPU compiler broadcasts from a constant and gives no name; on a
        # TPU a kernel is one custom call
        constant = experts == "pallas" and not entry["op_name"] and all(
            opcode == "broadcast" for opcode, _ in entry["inner"])
        if not constant and entry["opcode"] in (
                "fusion", "custom-call", "dot", "scatter", "gather", "sort",
                "while"):
            assert phase != "unplaced", entry
        _, scope, _ = opindex.place(
            entry, scopes=("attn", "block_attention", "route", "experts"))
        if layer in ("layer_1", "layer_2"):
            seen.add((phase, scope))
    for scope in ("attn", "block_attention", "route", "experts"):
        assert ("forward", scope) in seen and ("backward", scope) in seen


def _count(name, **labels):
    for family in get_registry().collect():
        if family.name == name:
            return sum(s.value for s in family.samples
                       if all(s.labels.get(k) == v
                              for k, v in labels.items()))
    return 0.0


def test_trace_time_counters(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    before = {
        "xla": _count("dl4j_block_attention_calls_total", backend="xla"),
        "fwd": _count("dl4j_block_attention_calls_total", backend="pallas",
                      direction="forward"),
        "bwd": _count("dl4j_block_attention_calls_total", backend="pallas",
                      direction="backward"),
        "loop": _count("dl4j_moe_grouped_matmul_calls_total",
                       backend="xla_chunks"),
        "kernels": _count("dl4j_moe_grouped_matmul_calls_total",
                          backend="pallas")}
    q, k, v, g = _qkv(128, 2, 1, 128)
    jax.grad(lambda q: jnp.sum(att.block_diffusion_mha(
        q, k, v, seq_len=128, block_len=4) * g))(q)
    q, k, v, _ = _qkv(8, 2, 1, 16)
    att.block_diffusion_mha(q, k, v, seq_len=8, block_len=4)
    _expert_net(4, 0).output(np.zeros((1, 4, D), np.float32))
    _expert_net(4, 0, d=WIDE, f=WIDE).output(
        np.zeros((1, 4, WIDE), np.float32))
    assert _count("dl4j_block_attention_calls_total",
                  backend="xla") == before["xla"] + 1
    assert _count("dl4j_block_attention_calls_total", backend="pallas",
                  direction="forward") == before["fwd"] + 1
    assert _count("dl4j_block_attention_calls_total", backend="pallas",
                  direction="backward") == before["bwd"] + 1
    # widths of 32 and 24 keep the chunk loop, whole lane tiles do not
    assert _count("dl4j_moe_grouped_matmul_calls_total",
                  backend="xla_chunks") == before["loop"] + 1
    assert _count("dl4j_moe_grouped_matmul_calls_total",
                  backend="pallas") == before["kernels"] + 1


def test_routing_counts_are_scraped_not_read_by_the_fit():
    from deeplearning4j_tpu.observability import moe
    small = zoo.sdar_moe(seed=3, **SMALL)
    small.fit(ListDataSetIterator(_ring(2, seed=9)))
    counted = moe.expert_rows(small)
    assert set(counted) == {"layer_1", "layer_2"}
    snapshot = get_registry().snapshot()
    by_layer = {s["labels"]["layer"]: s["value"]
                for s in snapshot["dl4j_moe_pairs_total"]}
    assert by_layer["layer_1"] == counted["layer_1"][1].sum() > 0
    experts = {s["labels"]["expert"] for s in snapshot["dl4j_moe_expert_rows"]
               if s["labels"]["layer"] == "layer_2"}
    assert experts == {"4", "5", "6", "7"}       # first_expert = 4
    del small                                    # the collector lets go


def test_the_total_of_rows_carries_past_int32():
    """``expert_rows_total`` is two int32 limbs: a step's counts added
    to a total a step short of 2**31 carry, where one int32 would wrap."""
    layer = _expert_net(2, 0).layers[0]
    near = (1 << 31) - 5
    state = {"expert_rows": jnp.zeros((2,), jnp.int32),
             "expert_rows_total": jnp.asarray(
                 [[near & ((1 << 30) - 1)] * 2, [near >> 30] * 2], jnp.int32)}
    a = jax.random.normal(jax.random.PRNGKey(0), (1, 40, D), jnp.float32)
    _, after = layer.apply(_expert_net(2, 0).params["layer_0"], state, a)
    rows = np.asarray(after["expert_rows"], np.int64)
    assert rows.sum() > 10 and after["expert_rows_total"].dtype == jnp.int32
    np.testing.assert_array_equal(
        rows_total(np.asarray(after["expert_rows_total"])), near + rows)


def test_the_zoo_router_starts_balanced_between_the_shares():
    """Every share's router columns start as copies of the first share's,
    so whichever share a chip holds it is given exactly one pair a row,
    for every seed; the embedding rows are normal(0, 1)."""
    ids = jnp.asarray(_ring(1, seed=7)[0].features)
    for seed, first in ((1, 0), (2, 4), (3, 6)):
        net = zoo.sdar_moe(seed=seed, **{**SMALL, "first_expert": first})
        wr = np.asarray(net.params["layer_1"]["Wr"])
        np.testing.assert_array_equal(wr[:, :4], wr[:, 12:])
        _, state = net._forward(net.params, net.state, ids, train=False,
                                rng=None)
        for name in ("layer_1", "layer_2"):
            assert int(np.sum(state[name]["expert_rows"])) == ids.size
    assert abs(float(jnp.std(net.params["layer_0"]["W"])) - 1.0) < 0.05
    with pytest.raises(ValueError, match="do not divide"):
        zoo.sdar_moe(seed=1, **{**SMALL, "experts_held": 5})


def test_the_references_adam_is_the_updaters(net):
    """``ref.adam`` on the gradients of the batches a fit saw, at the
    cell's rate, which hardly moves the parameters, gives the first
    moment and the change the fit left. 5e-3: the gradients of steps 2..8
    are taken at the initial parameters, and the two sides' own agree to
    1e-4 (above). 5e-2 for the change: 8e-7 of an
    entry near 0.02, whose float32 neighbours lie 1.9e-9 apart (those
    of an embedding entry near 1 lie 1.2e-7 apart: not compared)."""
    ring = _ring(2, seed=5)
    small = _untied(zoo.sdar_moe(seed=4, learning_rate=1e-7, **SMALL), 13)
    before = jax.tree_util.tree_map(np.asarray, small.params)
    small.fit(ListDataSetIterator(ring * 4), multi_step=8,
              device_prefetch=True)
    grads = [jax.grad(lambda p: ref.loss(
        p, None, jnp.asarray(ds.features), jnp.asarray(ds.labels),
        jnp.asarray(ds.labels_mask), **HOW))(before) for ds in ring]
    moment, change = ref.adam(grads, before, 8, learning_rate=1e-7)
    for name in ("layer_0", "layer_1", "layer_4"):
        for leaf in before[name]:
            _close(small.opt_state[name]["m"][leaf], moment[name][leaf],
                   rtol=5e-3)
            if name != "layer_0" and not leaf.endswith("_g"):
                _close(np.asarray(small.params[name][leaf])
                       - before[name][leaf], change[name][leaf], rtol=5e-2)

