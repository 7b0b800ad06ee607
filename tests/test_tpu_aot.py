"""Compiles for a TPU v5e that is described and not attached.

The TPU's compiler is installed where the tests run, so what Mosaic or
XLA would refuse on the chip (a slice off the tiling, too much VMEM) is
refused here, and the compiled text shows which ops the step is made of.
Nothing runs: no result and no time comes from this file. The topology
is described inside a fixture, never at import, and every test that
needs it lives in this one file (one process may load the TPU library).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu import zoo
from deeplearning4j_tpu.observability import opindex
from deeplearning4j_tpu.ops import lstm as lstm_ops

# the char-RNN cell of BENCHMARK.json: 256 sequences of 1,024 characters
T, B, N, VOCAB = 1024, 256, 512, 80


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def x32():
    # the suite turns x64 on (conftest.py); the chip runs without it, and
    # Mosaic refuses the i64 block indices that x64 traces
    with jax.enable_x64(False):
        yield


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _pallas_call(call, args):
    """The one ``pallas_call`` equation of ``call``."""
    eqns = [e for e in jax.make_jaxpr(call)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1, eqns
    return eqns[0]


@pytest.mark.parametrize("projected", [False, True],
                         ids=["given_xz", "projects_x"])
@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask"])
def test_lstm_kernels_compile_at_the_bench_shape(one_chip, x32, masked,
                                                 projected):
    cd = jnp.bfloat16
    seq = lambda width: jax.ShapeDtypeStruct((T, B, width), cd)
    row = jax.ShapeDtypeStruct((B, N), cd)
    Wh, p = (jax.ShapeDtypeStruct(s, cd) for s in ((N, 4 * N), (3, N)))
    mask = jax.ShapeDtypeStruct((T, B), cd) if masked else None
    fwd_args = (seq(4 * N), row, row, Wh, p, mask)
    if projected:
        # layer 0 of the cell: the forward is given the 80 one-hot columns
        # (a K that is no whole lane tile, which Mosaic takes), Wx and the
        # bias, and the same backward follows it (layer 1's 512 columns:
        # the test below compiles them in the step)
        fwd_args = (seq(VOCAB),) + fwd_args[1:] + (
            jax.ShapeDtypeStruct((VOCAB, 4 * N), cd),
            jax.ShapeDtypeStruct((4 * N,), cd))
    # residuals (G, hk, c_prev, h0, mask, Wh, p) and the cotangents
    bwd_args = ((seq(4 * N), seq(N), seq(N), row, mask, Wh, p),
                (seq(N), row, row))
    # the blocking the shapes choose: T = 1,024 gives the longest rung,
    # and Mosaic takes the (Tb, b, width) blocks, the unrolled body and
    # the VMEM the call asks for
    tb = lstm_ops._TIME_BLOCKS[0]
    assert tb > 1 and T % tb == 0
    for call, args in ((lstm_ops._fwd_call, fwd_args),
                       (lstm_ops._bwd_call, bwd_args)):
        eqn = _pallas_call(call, args)
        assert eqn.params["grid_mapping"].grid == (T // tb,)
        limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert limit <= lstm_ops._VMEM_CAP
        compiled = jax.jit(call).lower(*_on(one_chip, args)).compile()
        text = compiled.as_text()
        # the lowered text holds the kernel as MLIR bytecode; what it
        # shows of the call is the target and the VMEM it was given
        assert "tpu_custom_call" in text
        assert f'"size":"{limit}"' in text
    # one hidden stream: hk, hT, cT, G, c_prev and no h_prev; a mask
    # operand only where a mask was given; the backward reads hk twice,
    # as the block's own rows and as the one row before them
    fwd_out = jax.eval_shape(lstm_ops._fwd_call, *fwd_args)
    assert [o.shape for o in fwd_out] == [
        (T, B, N), (B, N), (B, N), (T, B, 4 * N), (T, B, N)]
    assert len(_pallas_call(lstm_ops._fwd_call, fwd_args).invars) == (
        5 + masked + 2 * projected)
    assert len(_pallas_call(lstm_ops._bwd_call, bwd_args).invars) == (
        10 + masked)
    # dxz, dh0, dc0, dWh, dp, and the bias gradient as one row
    db = jax.eval_shape(lstm_ops._bwd_call, *bwd_args)[-1]
    assert (db.shape, db.dtype) == ((1, 4 * N), cd)


def test_char_rnn_step_has_no_pass_over_dxz_or_xz(one_chip, x32, monkeypatch):
    # the step the benchmark's char-RNN cell runs. jax.default_backend()
    # is the CPU here, so the support gate is steered; the lowering is for
    # the described chip, where the kernels are Mosaic's.
    monkeypatch.setattr(lstm_ops, "_pallas_supported", lambda *a: True)
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
    net = zoo.char_rnn(vocab_size=VOCAB, hidden=N, n_layers=2)
    batch = jax.ShapeDtypeStruct((B, T, VOCAB), jnp.float32,
                                 sharding=one_chip)
    compiled = net._build_train_step().lower(
        *_on(one_chip, (net.params, net.state, net.opt_state,
                        jnp.zeros((), jnp.int32))),
        batch, batch, None, None,
        _on(one_chip, jax.random.PRNGKey(0))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    ops = {name: [(e["opcode"], e["op_name"])] + e["inner"]
           for name, e in opindex.parse(text).items()}
    for layer in ("layer_0", "layer_1"):
        # the bias gradient is the backward kernel's: no reduce of this
        # layer's backward is left (it read the 1.07 GB dxz to make 4 KB)
        reduces = [n for n, inner in ops.items() if any(
            op == "reduce" and f"transpose(jvp({layer}))" in name
            for op, name in inner)]
        assert not reduces, reduces
    for layer in ("layer_0", "layer_1"):
        # both layers read rows no wider than their hidden state (80
        # one-hot columns, 512): the forward kernel projects them, and no
        # matmul or bias add of a layer's forward stands outside the
        # kernels, nor anything else that makes a [t, b, 4n] array there
        # (layer 0's matmul wrote 1.07 GB of xz for the kernel to read
        # back, layer 1's the same)
        outside = [n for n, inner in ops.items() if any(
            op in ("convolution", "add") and f"/jvp({layer})/" in name
            for op, name in inner)]
        assert not outside, outside
        assert f"bf16[{T},{B},{4 * N}]" not in "".join(
            line for line in text.splitlines()
            if f"/jvp({layer})/" in line and "tpu_custom_call" not in line
            and "get-tuple-element" not in line)
    # the one-hot input has no gradient: dxz of layer 0 is contracted
    # with x for dWx and never with Wx, as layer 1's is for its input
    for layer in ("layer_0", "layer_1"):
        assert f"/transpose(jvp({layer}))/tbf,tbg->fg/dot_general" in text
    assert "/transpose(jvp(layer_0))/tbg,fg->tbf/dot_general" not in text
    assert "/transpose(jvp(layer_1))/tbg,fg->tbf/dot_general" in text


def test_block_attention_kernels_compile_at_the_bench_shape(one_chip, x32):
    """The forward and the fused backward kernel of ops/attention.py at
    the SDAR cell's shape: 2 x 4,096 rows, 32 query heads on 4 key/value
    heads of 128, blocks of 4 (Mosaic accepts the tiles, the scalar
    prefetch tables, the backward's resident dK and dV of 8,192 rows and
    the 64 MB VMEM limit): two kernels, where the split backward's dQ and
    dK/dV kernels made three."""
    from deeplearning4j_tpu.ops import attention as att

    seq, block = 4096, 4
    q, k, v = (jax.ShapeDtypeStruct((1, 2 * seq, h, 128), jnp.bfloat16,
                                    sharding=one_chip) for h in (32, 4, 4))

    def loss(q, k, v):
        out = att._bd_join(att._bd_attention(*att._bd_split(q, k, v), seq,
                                             block), 1)
        return jnp.sum(out.astype(jnp.float32))

    assert att._bd_fused_fits(2 * seq, 128, jnp.bfloat16)
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # live tiles only: 320 of the 1,024 tile pairs of 128 x 512
    assert len(att._bd_live_tiles(seq, block, 128, 512)) == 320


def test_grouped_expert_kernels_compile_at_the_bench_shape(one_chip, x32):
    """The three kernels of ops/grouped.py at the SDAR cell's shape:
    8,192 rows of 2,048, 16 held experts of width 768, the worst case of
    65,536 pairs and blocks of 16 x 768 (Mosaic accepts the transposed
    products, the scalar-prefetch tables and three float32 gradient
    blocks of one expert resident within the VMEM limit, the rows' DMAs).
    Forward and both backward kernels, each once in the program, inside
    the loop over the blocks of pairs."""
    from deeplearning4j_tpu.ops import grouped

    rows, d, f, held, pairs, chunk = 8192, 2048, 768, 16, 65536, 768
    cd = jnp.bfloat16
    shapes = [((rows, d), cd), ((pairs,), jnp.int32), ((pairs,), jnp.float32),
              ((held,), jnp.int32), ((held, d, f), cd), ((held, d, f), cd),
              ((held, f, d), cd)]
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]
    assert grouped.grouped_supported(*(args[i] for i in (0, 4, 5, 6)), pairs,
                                     chunk) == (jax.default_backend() == "tpu")
    assert grouped._vmem_request(d, f, 2) <= grouped._VMEM_CAP

    def loss(x, rows, coef, counts, wg, wu, wd):
        y = grouped._expert_ffn(x, rows, coef, counts, wg, wu, wd, chunk,
                                True)
        return jnp.sum(y * y)

    text = jax.jit(jax.grad(loss, (0, 2, 4, 5, 6))).lower(*args).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_hybrid_decoder_kernels_compile_at_the_bench_shape(one_chip, x32):
    """What the cell ``nemotron3_nano_30b_a3b-train-b1-l4096`` asks of
    the chip's compilers at its own shape: the tiled attention kernels
    under the causal tables (4,096 rows, 32 query heads on 2 key/value
    heads of 128: 16 heads a group, 2,048 rows a tile against 512 keys,
    within the 64 MB VMEM limit; the forward and the fused backward, two
    kernels), and the two kernels of the chunked
    recurrence (64 heads of 64 in pairs, state 128, 8 groups, 32 chunks
    of 128: a step is a group's chunk, its decay tiles never leave
    VMEM). The registry would hand this CPU process the xla executor, so
    the kernels' entry is compiled itself."""
    from deeplearning4j_tpu.ops import attention as att
    from deeplearning4j_tpu.ops import ssm

    seq, cd = 4096, jnp.bfloat16
    q, k, v = (jax.ShapeDtypeStruct((1, seq, h, 128), cd, sharding=one_chip)
               for h in (32, 2, 2))

    def attend(q, k, v):
        out = att._bd_join(att._causal_tiled(*att._bd_split(q, k, v)), 1)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(attend, (0, 1, 2))).lower(q, k, v).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # live tiles only: 144 of the 256 tile pairs of 128 x 512
    assert len(att._causal_live_tiles(seq, 128, 512)) == 144

    def scan_args(rows, heads, p):
        shapes = [((1, rows, heads, p), cd), ((1, rows, heads), jnp.float32),
                  ((heads,), jnp.float32), ((1, rows, 8, 128), cd),
                  ((1, rows, 8, 128), cd), ((heads,), jnp.float32)]
        return [jax.ShapeDtypeStruct(s, t, sharding=one_chip)
                for s, t in shapes]

    args = scan_args(seq, 64, 64)
    assert ssm.ssm_scan_supported(*args, 128) is False      # no TPU here

    def scan(*a):
        return jnp.sum(ssm._tiled(*a, 128))

    # a head a lane tile takes other lines of the kernels: Mosaic
    # spreads no [1, 1] value over a tile
    wide = jax.jit(jax.grad(scan, tuple(range(6)))).lower(
        *scan_args(512, 32, 128)).compile()
    assert wide.as_text().count('custom_call_target="tpu_custom_call"') == 2

    compiled = jax.jit(jax.grad(scan, tuple(range(6)))).lower(*args).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    # 135 MB as compiled: the states before every chunk that the forward
    # saves (67 MB) and the float32 gradient this sum hands the backward
    # (67 MB); the xla executor's decay matrices and their products held
    # several hundred
    assert compiled.memory_analysis().temp_size_in_bytes < 2e8


def test_latent_decoder_kernels_compile_at_the_bench_shape(one_chip, x32):
    """What the cell ``glm4_7_flash-train-b1-l4096`` asks of the chip's
    compilers at its own shape: the tiled attention kernels under the
    causal tables with 20 query heads on 20 key/value heads of 256 (a
    group of 1, which the rule gives a query tile of 512: 512 rows a
    grid step against 512 keys, 36 live tile pairs a head, within the
    64 MB VMEM limit; the forward and the fused backward, two kernels),
    and the grouped experts' kernels at a hidden
    width of 1,536, whose three matrices pass the VMEM cap whole and run
    as two slices of 768 inside one block (the forward and both backward
    kernels twice each)."""
    from deeplearning4j_tpu.nn.layers.decoder import expert_chunk_rows
    from deeplearning4j_tpu.ops import attention as att
    from deeplearning4j_tpu.ops import grouped

    seq, cd = 4096, jnp.bfloat16
    q, k, v = (jax.ShapeDtypeStruct((1, seq, 20, 256), cd, sharding=one_chip)
               for _ in range(3))

    def attend(q, k, v):
        out = att._bd_join(att._causal_tiled(*att._bd_split(q, k, v)), 1)
        return jnp.sum(out.astype(jnp.float32))

    assert att._bd_query_tile(1, seq) == att._bd_key_tile(seq) == 512
    # live tiles only: 36 of the 64 tile pairs of 512 x 512; Mosaic takes
    # them with ``vmem_limit_bytes=_BD_VMEM_LIMIT`` or raises here
    assert len(att._causal_live_tiles(seq, 512, 512)) == 36
    text = jax.jit(jax.grad(attend, (0, 1, 2))).lower(q, k, v).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2

    rows, d, f, held, experts, top_k = seq, 2048, 1536, 8, 64, 4
    pairs, chunk = rows * top_k, expert_chunk_rows(rows, top_k, experts)
    assert chunk == 384                 # 256 pairs an expert expected
    shapes = [((rows, d), cd), ((pairs,), jnp.int32), ((pairs,), jnp.float32),
              ((held,), jnp.int32), ((held, d, f), cd), ((held, d, f), cd),
              ((held, f, d), cd)]
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]
    assert grouped._vmem_request(d, f, 2) > grouped._VMEM_CAP
    assert grouped._width_slices(d, f, 2) == 2
    assert grouped._vmem_request(d, f // 2, 2) <= grouped._VMEM_CAP

    def loss(x, rows, coef, counts, wg, wu, wd):
        y = grouped._expert_ffn(x, rows, coef, counts, wg, wu, wd, chunk,
                                True)
        return jnp.sum(y * y)

    text = jax.jit(jax.grad(loss, (0, 2, 4, 5, 6))).lower(*args).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6


def test_short_conv_decoder_kernels_compile_at_the_bench_shape(one_chip, x32):
    """What the cell ``lfm2_24b_a2b-train-b1-l8192`` asks of the chip's
    compilers at its own shape: the gated short convolution's forward
    and backward kernels on ``[1, 8192, 3 * 2048]`` bf16 (32 time tiles
    of 256 positions and all 6,144 columns, the halo a second block of
    16 rows, the filter's gradient one resident block), and the tiled
    attention kernels under the causal tables with 32 query heads on 8
    key/value heads of 64: the head is the blocks' whole last dimension,
    a group of 4 takes a query tile of 256 against 512 keys, and the
    fused backward keeps dK and dV of 8,192 rows resident. The
    registry would hand this CPU process the xla executor, so the
    kernels' entries are compiled themselves."""
    from deeplearning4j_tpu.ops import attention as att
    from deeplearning4j_tpu.ops import shortconv

    seq, d, cd = 8192, 2048, jnp.bfloat16
    bcx = jax.ShapeDtypeStruct((1, seq, 3 * d), cd, sharding=one_chip)
    w = jax.ShapeDtypeStruct((d, 3), jnp.float32, sharding=one_chip)
    assert shortconv.short_conv_supported(bcx, w) is False      # no TPU here
    assert shortconv._time_tile(seq) == 256

    def conv(bcx, w):
        return jnp.sum(jnp.square(shortconv._tiled(bcx, w).astype(
            jnp.float32)))

    compiled = jax.jit(jax.value_and_grad(conv, (0, 1))).lower(
        bcx, w).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    # no copy of B, C or x~ beside the kernels' own operands: the
    # temporaries are y, its float32 square's gradient and dw
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e8

    q, k, v = (jax.ShapeDtypeStruct((1, seq, h, 64), cd, sharding=one_chip)
               for h in (32, 8, 8))

    def attend(q, k, v):
        out = att._bd_join(att._causal_tiled(*att._bd_split(q, k, v)), 1)
        return jnp.sum(out.astype(jnp.float32))

    assert att._bd_query_tile(4, seq) == 256 and att._bd_key_tile(seq) == 512
    # live tiles only: 272 of the 512 tile pairs of 256 x 512
    assert len(att._causal_live_tiles(seq, 256, 512)) == 272
    text = jax.jit(jax.grad(attend, (0, 1, 2))).lower(q, k, v).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_sparse_decoder_kernels_compile_at_the_bench_shape(one_chip, x32):
    """What the cell ``keye_vl2_30b_a3b-train-b1-l8192`` asks of the
    chip's compilers at its own shape, 8,192 rows: the selection kernel
    (16 indexer heads of 64 and one key head, a top 2,048: a query tile
    of 128 rows holds its 8,192 keys' int32 images in 4 MB of VMEM), the
    tiled attention forward and one-kernel backward under the selection's
    bits (32 query heads on 4 key/value heads of 128, 128 rows a tile
    against 512 keys, the causal pairs' table made from the bits), and
    the indexer's loss with its gradient in one kernel. The registry
    would hand this CPU process the xla executors, so the kernels'
    entries are compiled themselves."""
    from deeplearning4j_tpu.ops import attention as att
    from deeplearning4j_tpu.ops import sparse_attention as sa

    seq, cd = 8192, jnp.bfloat16
    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)
    q_index, k_index = shape((1, seq, 16, 64), cd), shape((1, seq, 64), cd)
    w = shape((1, seq, 16), jnp.float32)
    sel = shape((1, seq // 32, seq), jnp.int32)
    q, k, v = (shape((1, seq, h, 128), cd) for h in (32, 4, 4))
    assert sa.dsa_select_supported(q_index, k_index, w, 2048) is False
    assert sa._tiled_length(seq)

    select = jax.jit(lambda a, b, c: sa._select_tiled(a, b, c, 2048)).lower(
        q_index, k_index, w).compile()
    assert select.as_text().count('custom_call_target="tpu_custom_call"') == 1

    def attend(q, k, v, sel):
        tables, steps = sa._tables(sel, 128, 512)
        og, lse = sa._sel_attention(*att._bd_split(q, k, v), sel, tables,
                                    steps)
        return jnp.sum(att._bd_join(og, 1).astype(jnp.float32))

    assert att._bd_query_tile(8, seq) == 128 and att._bd_key_tile(seq) == 512
    # 544 causal tile pairs of 128 x 512, the grid's length; the walk
    # skips those without a kept key
    assert len(att._causal_live_tiles(seq, 128, 512)) == 544
    text = jax.jit(jax.grad(attend, (0, 1, 2))).lower(q, k, v, sel).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2

    lse, lse_index = shape((1, 32, seq), jnp.float32), shape((1, seq),
                                                            jnp.float32)
    loss = jax.jit(jax.grad(sa._kl_tiled, (3, 4, 5))).lower(
        q, k, lse, q_index, k_index, w, sel, lse_index).compile()
    assert loss.as_text().count('custom_call_target="tpu_custom_call"') == 1


def test_sparse_decoder_step_fits_the_chip(one_chip, x32, monkeypatch):
    """The whole training step of the cell, compiled for a described
    v5e: 28 Pallas calls (a selection, an attention forward and backward,
    the indexer's loss and three expert kernels in each of the 4 layers),
    and its arguments and temporaries within the chip's 16 GB."""
    from unittest import mock

    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    init = MultiLayerNetwork.init
    with mock.patch.object(MultiLayerNetwork, "init",
                           lambda self, seed=None, structure_only=False:
                           init(self, seed, structure_only=True)):
        net = zoo.keye_vl2_moe(n_layers=4, experts_held=16,
                               vocab_size=18992, learning_rate=1e-7)
    ids = jnp.zeros((1, 8192), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = net._step_args(net._batch_args(DataSet(ids, ids)),
                          jax.random.PRNGKey(0))
    compiled = jax.jit(net._step_fn(), donate_argnums=(0, 1, 2)).lower(
        *_on(one_chip, args)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 28
    # no float array of a (row, key) pair: the selection is int32 bits
    assert not [shape for shape in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]",
                                              text)
                if shape.split(",").count("8192") >= 2]
    memory = compiled.memory_analysis()
    # 465 M float32 parameters and Adam's two moments are 5.58 GB
    assert abs(memory.argument_size_in_bytes - 5.585e9) < 0.01e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16e9
