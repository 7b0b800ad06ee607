"""The tiled attention forward cut into chunks of query lanes.

``ops/attention.py``'s ``fwd_body`` makes a grid step's [keys, g * bq]
scores in ``_fwd_chunks`` independent chunks of lanes. A lane is one row
(a head's query position) and every row's statistics lie in its own
lane, so the chunked form is the one-chunk form's arithmetic, row by
row. These tests hold it to that in interpret mode at each decoder
cell's tile geometry, under the block rule, the causal rule and a
selection made from data, and check the chunk rule and its counter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import sparse_attention as sa
from tests.test_sdar_moe import _count

# (cell, query heads a key/value head, head size, rows, rule); rule:
# ("block", seq_len, block_len), ("causal",), ("select", how). The cells'
# (g, bq, bk, dh) at rows the interpreter runs in a second: the tiles
# follow the group and the length (``_bd_query_tile``, ``_bd_key_tile``),
# asserted below.
CELLS = [
    ("sdar_30b_a3b-train-b1-l4096", 8, 128, 1024, ("block", 512, 4)),
    ("keye_vl2_30b_a3b-train-b1-l8192", 8, 128, 1024, ("select", "indexer")),
    ("glm4_7_flash-train-b1-l4096", 1, 256, 1024, ("causal",)),
    ("lfm2_24b_a2b-train-b1-l8192", 4, 64, 1024, ("causal",)),
    ("nemotron3_nano_30b_a3b-train-b1-l4096", 16, 128, 1024, ("causal",)),
    # a group of 1 under a selection: a chunk is part of one head's rows,
    # and in the tile pair (query tile 1, key tile 0) the first chunk's
    # rows keep no key while the second chunk's do
    ("a chunk of a walked tile keeps no key", 1, 128, 1024,
     ("select", "empty_chunk")),
    # groups whose 640 and 768 lanes split in two at no whole head: the
    # rule keeps one chunk, and the chunks there are can be one head
    # (5 of 128 lanes; 3 of 256) or half of one (6 of 128)
    ("a group of 5", 5, 128, 1024, ("causal",)),
    ("a group of 5 under a selection", 5, 128, 1024, ("select", "indexer")),
    ("a group of 3", 3, 128, 1024, ("causal",)),
    ("a group of 3 under a selection", 3, 128, 1024, ("select", "indexer")),
]

TILES = {  # (g, bq, bk, dh) of a case's grid step at its rows; a cell's
    # are those of its own length
    "sdar_30b_a3b-train-b1-l4096": (8, 128, 512, 128),
    "keye_vl2_30b_a3b-train-b1-l8192": (8, 128, 512, 128),
    "glm4_7_flash-train-b1-l4096": (1, 512, 512, 256),
    "lfm2_24b_a2b-train-b1-l8192": (4, 256, 512, 64),
    "nemotron3_nano_30b_a3b-train-b1-l4096": (16, 128, 512, 128),
    "a chunk of a walked tile keeps no key": (1, 512, 512, 128),
    "a group of 5": (5, 128, 512, 128),
    "a group of 5 under a selection": (5, 128, 512, 128),
    "a group of 3": (3, 256, 512, 128),
    "a group of 3 under a selection": (3, 256, 512, 128),
}


def _cuts(g, bq):
    """Every chunk count of two or more that cuts a step's lanes into
    chunks of whole heads or equal slices of one head, 128 lanes at
    least: the counts the forward body can run."""
    return [n for n in range(2, g * bq // 128 + 1)
            if g % n == 0 or (n % g == 0 and bq % (n // g) == 0)]


def _operands(rows, g, dh, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (1, rows, h, dh), jnp.float32).astype(
        jnp.bfloat16) for k, h in zip(ks, (g, 1, 1))]


def _selection(rows, how):
    """Bits made from data: the top quarter of a seeded indexer's scores
    a row, or a causal mask with a chunk of rows moved off key tile 0."""
    if how == "indexer":
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q_index = jax.random.normal(ks[0], (1, rows, 2, 64), jnp.bfloat16)
        k_index = jax.random.normal(ks[1], (1, rows, 64), jnp.bfloat16)
        w = jax.random.normal(ks[2], (1, rows, 2), jnp.float32)
        return sa.dsa_select_xla(q_index, k_index, w, topk=rows // 4)[0]
    keep = np.tril(np.ones((rows, rows), bool))
    keep[512:768, :512] = False     # rows 512-767 see keys 512-767 alone
    return sa.pack(jnp.asarray(keep[None]))


def _forward(rows, g, dh, rule, chunks):
    """(o, lse) of the forward kernel at ``chunks`` chunks a step."""
    q, k, v = _operands(rows, g, dh)
    qg, kg, vg = att._bd_split(q, k, v)
    if rule[0] == "select":
        sel = _selection(rows, rule[1])
        fn = lambda: sa._sel_forward(
            qg, kg, vg, sel, *sa._tables(sel, att._bd_query_tile(g, rows),
                                         att._bd_key_tile(rows)))
    elif rule[0] == "block":
        fn = lambda: att._bd_forward(qg, kg, vg, rule[1], rule[2])
    else:
        fn = lambda: att._bd_forward(qg, kg, vg, rows, None)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(att, "_fwd_chunks", lambda g, bq: chunks)
        return jax.jit(fn)()     # a fresh function: traced at this count


def _bf16_ulp(x):
    """The spacing of bf16 numbers at ``|x|`` (7 stored mantissa bits)."""
    return np.exp2(np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


@pytest.mark.parametrize("cell,g,dh,rows,rule", CELLS,
                         ids=[c[0] for c in CELLS])
def test_chunked_forward_is_the_one_chunk_form(monkeypatch, cell, g, dh,
                                               rows, rule):
    """At every chunk count the body can run (``_cuts``), the log-sum-exp
    equals the one-chunk form's bit for bit: the scores, the max and the
    sum of a row are the same operations on the same lanes. ``o`` equals
    it bit for bit, or where the interpreter's second product over a
    narrower chunk sums its keys in another order, no entry moves by
    more than one bf16 unit in the last place of its row's largest
    entry (the float32 sums differ in their last bits, and the rounding
    to bf16 shows it on a few entries). Which case was met is printed."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    seq = rule[1] if rule[0] == "block" else rows
    bq, bk = att._bd_query_tile(g, seq), att._bd_key_tile(seq)
    assert (g, bq, bk, dh) == TILES[cell]
    if rule == ("select", "empty_chunk"):
        words = np.asarray(sa.unpack(_selection(rows, "empty_chunk"))[0])
        assert not words[512:768, :512].any() and words[768:, :512].all()
    o_one, lse_one = _forward(rows, g, dh, rule, 1)
    o_one = np.asarray(o_one.astype(jnp.float32))
    counts = _cuts(g, bq)
    assert counts
    for n in counts:
        o, lse = _forward(rows, g, dh, rule, n)
        np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_one))
        o = np.asarray(o.astype(jnp.float32))
        moved = np.abs(o - o_one)
        row_ulp = _bf16_ulp(np.max(np.abs(o_one), axis=-1, keepdims=True))
        assert np.all(moved <= row_ulp), (cell, n, float(
            np.max(moved / row_ulp)))
        print(f"{cell}: {n} chunks of {g * bq // n} lanes: lse bit for bit, "
              + ("o bit for bit" if not moved.any() else
                 f"o within one bf16 unit of its row's largest entry at "
                 f"{int(np.count_nonzero(moved))} of {moved.size} entries"))


@pytest.mark.parametrize("cell,heads,kv_heads,dh,seq,rule,chunks", [
    ("sdar_30b_a3b-train-b1-l4096", 32, 4, 128, 4096, "block", 2),
    ("keye_vl2_30b_a3b-train-b1-l8192", 32, 4, 128, 8192, "select", 2),
    ("glm4_7_flash-train-b1-l4096", 20, 20, 256, 4096, "causal", 2),
    ("lfm2_24b_a2b-train-b1-l8192", 32, 8, 64, 8192, "causal", 2),
    ("nemotron3_nano_30b_a3b-train-b1-l4096", 32, 2, 128, 4096, "causal",
     4),
    # groups whose lanes split in two at no whole head keep one chunk;
    # twelve heads of 128 split in three at four heads each
    ("a group of 5", 40, 8, 128, 4096, "causal", 1),
    ("a group of 5 under a selection", 40, 8, 128, 8192, "select", 1),
    ("a group of 3", 24, 8, 128, 4096, "causal", 1),
    ("a group of 3 under a selection", 24, 8, 128, 8192, "select", 1),
    ("a group of 12", 48, 4, 128, 4096, "causal", 3)])
def test_forward_chunks_follow_the_shape(cell, heads, kv_heads, dh, seq,
                                         rule, chunks):
    """The chunks of a forward grid step are a function of the tile
    geometry the kernel sees (no option selects them), and
    ``dl4j_tiled_attention_forward_chunks_total{chunks, group}`` counts
    each traced forward call once, under a rule's table or a selection;
    the backward adds nothing to it. Traced at the cells' own shapes and
    at groups the cells do not have, nothing runs."""
    group = heads // kv_heads
    rows = 2 * seq if rule == "block" else seq
    bq, bk = att._bd_query_tile(group, seq), att._bd_key_tile(seq)
    assert att._fwd_chunks(group, bq) == chunks, cell
    q, k, v = (jax.ShapeDtypeStruct((1, rows, h, dh), jnp.bfloat16)
               for h in (heads, kv_heads, kv_heads))
    sel = jax.ShapeDtypeStruct((1, seq // 32, seq), jnp.int32)

    def attend(q, k, v, sel):
        qg, kg, vg = att._bd_split(q, k, v)
        if rule == "select":
            og = sa._sel_attention(qg, kg, vg, sel, *sa._tables(sel, bq, bk))[0]
        elif rule == "block":
            og = att._bd_attention(qg, kg, vg, seq, 4)
        else:
            og = att._causal_tiled(qg, kg, vg)
        return jnp.sum(att._bd_join(og, 1).astype(jnp.float32))

    def counts():
        return {n: _count("dl4j_tiled_attention_forward_chunks_total",
                          chunks=str(n), group=str(group))
                for n in range(1, 17)}

    before = counts()
    jax.eval_shape(attend, q, k, v, sel)
    forward = counts()
    assert {n: forward[n] - before[n] for n in forward} == {
        n: float(n == chunks) for n in forward}, cell
    jax.eval_shape(jax.grad(attend, (0, 1, 2)), q, k, v, sel)
    after = counts()
    # the gradient traces the forward once more and the backward not at all
    assert {n: after[n] - forward[n] for n in after} == {
        n: float(n == chunks) for n in after}, cell


@pytest.mark.parametrize("g,rule", [(5, "causal"), (5, "select"),
                                    (3, "causal"), (3, "select")])
def test_a_step_of_one_chunk_is_the_dense_masked_softmax(monkeypatch, g,
                                                         rule):
    """Where a step's lanes split at no whole head the forward keeps one
    chunk, and the kernels' output, log-sum-exp and every gradient are
    the XLA form's, under the causal rule and a selection."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    rows = 512
    assert att._fwd_chunks(g, att._bd_query_tile(g, rows)) == 1
    q, k, v = (x.astype(jnp.float32) for x in _operands(rows, g, 128))
    cot = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)
    sel = _selection(rows, "indexer")
    if rule == "select":
        assert sa.sparse_attention_supported(q, k, v, sel)
        forms = (lambda *a: sa.sparse_attention_pallas(*a, sel)[:2],
                 lambda *a: sa.sparse_attention_xla(*a, sel)[:2])
    else:
        assert att.causal_attention_supported(q, k, v)
        forms = (lambda *a: (att.causal_attention_pallas(*a),),
                 lambda *a: (att.causal_attention_xla(*a),))

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out[0] * cot), out
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
            q, k, v)

    (_, got), g_got = run(forms[0])
    (_, want), g_want = run(forms[1])
    for a, b in zip(got + g_got, want + g_want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max()
