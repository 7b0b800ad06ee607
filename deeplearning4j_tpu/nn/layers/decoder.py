"""Runtime layers of a modern decoder (configs and the equations:
nn/conf/layers_decoder.py; PERF.md section 4 has the four models they
were written for, and that docstring says which decoder uses what).

Precision under a mixed policy: parameters in the param dtype, every
large product in the compute dtype with float32 accumulation, and in
float32 throughout: the norms' statistics, the rotation, the router (its
product too, at ``Precision.HIGHEST``: which eight experts a row takes
must not turn on a bf16 rounding of the router's own making), the
attention's softmax and the loss. The residual stream between layers is
in the compute dtype, as in every other net of this package.

Named scopes inside a layer, under the layer's own: ``attn`` (norm,
projections, head norms, rotation, output projection, and inside it
``block_attention`` or ``causal_attention`` round the attention itself,
whatever backend runs; a sparse attention has ``dsa_indexer`` round the
indexer's three projections, its norm and rotation, ``dsa_select``
round its scores and top-k, ``sparse_attention`` round the attention
over the selection and ``dsa_kl`` round the indexer's loss; a latent
attention has two more inside it,
``mla_down`` round both compressions and their norms and ``mla_up``
round both expansions, the rotation and the broadcast of the rotated
key slice), ``dense_mlp`` (a dense layer's norm and three products),
``head`` and ``mtp`` (in ``MtpTokenOutput``: the model's final norm,
head and loss; and all the prediction module does, its own ``attn``,
``route``, ``experts`` and ``shared_expert`` inside it), ``mamba``
(norm, both projections, and inside it ``ssm_conv``, ``ssm_scan`` and
``ssm_norm`` round the three ops of ops/ssm.py), ``conv_op`` (a
short-convolution operator's norm and both projections, and inside it
``short_conv`` round the op of ops/shortconv.py alone), ``shared_expert`` (its
two or three products), ``route`` (norm, router, top-k, the one-hot
read of the chosen scores, the sort of the pairs that carries their
weights and the sort that takes the weights' gradient back, and what
``ops/grouped.py`` does to move rows in XLA: a gather a block of pairs
before its kernels, or a gather and a scatter-add a chunk inside its
loop; whole rows, never single scalars) and ``experts`` (the grouped
products and the gating between them: on a TPU one Pallas call a block
forward and two backward, which add their rows to the result
themselves; the chunk loop's dots elsewhere).
``observability/opindex.py`` places a device op by the innermost scope
it is asked about.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.attention import _layer_norm
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import grouped
from deeplearning4j_tpu.ops import initializers as init_mod
from deeplearning4j_tpu.ops import shortconv, ssm
from deeplearning4j_tpu.ops import sparse_attention as sa


def _rms_norm(x, g, eps):
    """In float32, returns float32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + float(eps)) * g.astype(jnp.float32)


def _project(x, w, cd):
    return jnp.einsum("btf,fg->btg", x.astype(cd), w.astype(cd))


def _rotate(x, theta, pos):
    """Rotary positions over the whole of ``x`` [b, t, h, dh]
    (rotate-half: column ``i`` pairs with ``i + dh/2``), float32; row
    ``i`` sits at ``pos[i]`` (int32 [t])."""
    dh = x.shape[3]
    pos = pos.astype(jnp.float32)
    inv = float(theta) ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = pos[:, None] * inv[None, :]                      # [t, dh/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _feed_forward(w, gate, up, down):
    """One expert every row takes, in the two forms of ops/grouped.py:
    ``down(silu(gate w) * up w)``, or ``down(max(up w, 0)^2)`` where
    ``gate`` is None. ``w`` [R, d] in the compute dtype; float32 [R, d]."""
    cd = w.dtype
    held = jnp.dot(w, up.astype(cd), preferred_element_type=jnp.float32)
    if gate is None:
        held = jnp.square(jnp.maximum(held, 0.0))
    else:
        held = jax.nn.silu(jnp.dot(
            w, gate.astype(cd), preferred_element_type=jnp.float32)) * held
    return jnp.dot(held.astype(cd), down.astype(cd),
                   preferred_element_type=jnp.float32)


def expert_chunk_rows(rows: int, experts_per_token: int,
                      n_experts: int) -> int:
    """The pairs one held expert expects from a layer that routes
    ``rows`` rows, half as many again to spare, in whole tiles of 128 and
    at most 1,024: the grain ``ops/grouped.py`` is told.

    Its chunk loop (the CPU, widths off the lane tile) runs chunks of so
    many pairs of one expert: an expert near its expected load is then
    one chunk whatever the batch, and a step's time follows the number
    of chunks that hold a pair (0.14% of the rate a chunk on the v5e
    when the loop ran there, PERF.md Findings PR 31). Its kernels (a
    TPU) take this many pairs times the held experts as one block of
    the sorted pairs, so the usual step is one block a layer and a
    biased router pays a second; their row tile is the kernels' own
    (``grouped.TILE``), and nothing else about them is sized here."""
    expected = rows * experts_per_token / n_experts
    return 128 * min(max(math.ceil(1.5 * expected / 128), 1), 8)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sort_pairs(key, coef, n_keys):
    """The stable order of ``key`` (int32 [P], values in ``0..n_keys-1``)
    and ``coef`` (float32 [P]) in that order, from ONE sort that carries
    both: ``argsort`` is this sort without the weights, and
    ``coef[order]`` beside it is a gather of ``P`` single scalars whose
    transpose is a scatter-add of ``P`` single scalars (7 and 9 ns a
    scalar on a v5e, PERF.md Findings PR 40). The gradient arrives in
    sorted order and goes back by sorting it on ``order``, a permutation:
    the same bits both ways."""
    pairs = key.shape[0]
    iota = jax.lax.iota(jnp.int32, pairs)
    if n_keys * pairs <= 2 ** 31:
        # one int32 holds a pair's key and its place, no two alike: a
        # sort of two operands with no ties to keep in order, which a
        # v5e's compiler builds in a third of the time of the stable
        # sort of three below, in 0.8 MB less code (PERF.md, PR 40)
        packed, coef = jax.lax.sort((key * pairs + iota, coef), num_keys=1,
                                    is_stable=False)
        return jax.lax.rem(packed, jnp.int32(pairs)), coef
    _, order, coef = jax.lax.sort((key, iota, coef), num_keys=1,
                                  is_stable=True)
    return order, coef


def _sort_pairs_fwd(key, coef, n_keys):
    order, coef = _sort_pairs(key, coef, n_keys)
    return (order, coef), order


def _sort_pairs_bwd(n_keys, order, cotangents):
    # ``order`` has no ties either
    return None, jax.lax.sort((order, cotangents[1]), num_keys=1,
                              is_stable=False)[1]


_sort_pairs.defvjp(_sort_pairs_fwd, _sort_pairs_bwd)


_LIMB = 30      # bits of the low limb of ``expert_rows_total``


def rows_total(limbs):
    """``expert_rows_total`` [2, experts] (host side) as int64 counts."""
    return (limbs[1].astype("int64") << _LIMB) + limbs[0]


class _DecoderLayer(Layer):
    """What the layers here share: the seeded init of a matrix, and no
    streaming state (they train and answer ``net.output``)."""

    is_recurrent_stateful = True    # so that set_streaming reaches them

    @property
    def streaming(self):
        return False

    @streaming.setter
    def streaming(self, flag):
        if flag:
            raise NotImplementedError(
                f"{type(self).__name__} '{self.name}' has no streaming "
                "path: rnn_time_step and truncated BPTT are not supported "
                "on the decoder layers (ROADMAP Queue 2 items 9 and 10)")

    def _init(self, key, shape, fan_in, fan_out):
        w_fn = init_mod.resolve(self.resolve("weight_init", "xavier"))
        return w_fn(key, shape, fan_in, fan_out, self.param_dtype)


class TokenEmbeddingLayer(_DecoderLayer):
    def init_params(self, key):
        n_in, n_out = int(self.conf.n_in), int(self.conf.n_out)
        return {"W": self._init(key, (n_in, n_out), n_in, n_out)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if not jnp.issubdtype(x.dtype, jnp.integer):
            raise TypeError(
                f"TokenEmbedding '{self.name}' takes integer ids [b, t], "
                f"got {x.dtype}{tuple(x.shape)}")
        return (jnp.take(params["W"], x, axis=0).astype(self.compute_dtype),
                state)


class RmsNormLayer(_DecoderLayer):
    def init_params(self, key):
        return {"g": jnp.ones((int(self.conf.n_out),), self.param_dtype)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y = _rms_norm(x, params["g"], self.conf.eps)
        return y.astype(self.compute_dtype), state


class RoutedExpertsLayer(_DecoderLayer):
    def __init__(self, conf, input_type, global_conf, policy):
        super().__init__(conf, input_type, global_conf, policy)
        self.held = int(conf.n_experts if conf.experts_held is None
                        else conf.experts_held)
        if not 0 <= conf.first_expert <= conf.n_experts - self.held:
            raise ValueError(
                f"{type(conf).__name__} '{conf.name}': experts "
                f"{conf.first_expert}..{conf.first_expert + self.held - 1} "
                f"are not among {conf.n_experts}")
        for field, known in (("router", ("softmax", "sigmoid")),
                             ("expert_form", ("gated_silu", "relu2"))):
            if getattr(conf, field) not in known:
                raise ValueError(
                    f"{type(conf).__name__} '{conf.name}': {field} "
                    f"{getattr(conf, field)!r} is neither "
                    f"{known[0]!r} nor {known[1]!r}")

    def init_params(self, key):
        d, f = int(self.conf.n_out), int(self.conf.expert_width)
        n, held = int(self.conf.n_experts), self.held
        kr, kg, ku, kd = jax.random.split(key, 4)
        params = {
            "ln_g": jnp.ones((d,), self.param_dtype),
            "Wr": self._init(kr, (d, n), d, n),
            "Wu": self._init(ku, (held, d, f), d, f),
            "Wd": self._init(kd, (held, f, d), f, d),
        }
        if self.conf.expert_form == "gated_silu":
            params["Wg"] = self._init(kg, (held, d, f), d, f)
        shared = int(self.conf.shared_width)
        if shared:
            ku, kd = jax.random.split(jax.random.fold_in(key, 1))
            params["Ws_u"] = self._init(ku, (d, shared), d, shared)
            params["Ws_d"] = self._init(kd, (shared, d), shared, d)
            if self.conf.expert_form == "gated_silu":
                params["Ws_g"] = self._init(jax.random.fold_in(key, 2),
                                            (d, shared), d, shared)
        return params

    def init_state(self):
        # the total in two int32 limbs (low 30 bits, the rest): one would
        # wrap after 33k steps of the worst case
        state = {"expert_rows": jnp.zeros((self.held,), jnp.int32),
                 "expert_rows_total": jnp.zeros((2, self.held), jnp.int32)}
        if self.conf.router == "sigmoid":
            state["router_bias"] = jnp.zeros((int(self.conf.n_experts),),
                                             jnp.float32)
        return state

    def _choose(self, logits, state):
        """The ``experts_per_token`` experts of every row and their
        weights, float32 [R, k]. ``top_k`` gives the indices alone; a
        row's chosen scores are read by a one-hot compare, a sum with one
        term that is not zero, whose gradient is such a sum too (a row's
        choices are distinct): top-k's own values, or ``take_along_axis``,
        come back as a scatter-add of ``R k`` single scalars."""
        k = int(self.conf.experts_per_token)
        sigmoid = self.conf.router == "sigmoid"
        if sigmoid:
            score = jax.nn.sigmoid(logits)
            ranked = score + jax.lax.stop_gradient(state["router_bias"])
        else:
            score = ranked = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(ranked, k)
        experts = jnp.arange(score.shape[-1], dtype=chosen.dtype)
        top = jnp.sum(jnp.where(chosen[..., None] == experts,
                                score[..., None, :], 0.0), axis=-1)
        total = jnp.sum(top, axis=-1, keepdims=True)
        if sigmoid:
            total = total + float(self.conf.router_eps)
        return chosen, top / total

    def _route(self, params, state, a):
        """The normed rows [R, d] (float32) and the pairs held here,
        sorted by expert: rows, weights, and the count of each expert.
        All ``R k`` pairs are sorted, those of experts held elsewhere
        last, and the weights ride the sort (``_sort_pairs``): nothing
        here moves a single scalar by index, forward or backward."""
        k = int(self.conf.experts_per_token)
        w = _rms_norm(a, params["ln_g"], self.conf.eps).reshape(
            -1, a.shape[-1])
        chosen, coef = self._choose(jnp.dot(
            w, params["Wr"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), state)
        if self.conf.routed_scale != 1.0:
            coef = coef * float(self.conf.routed_scale)
        local = chosen.astype(jnp.int32) - int(self.conf.first_expert)
        key = jnp.where((local >= 0) & (local < self.held), local,
                        self.held).reshape(-1)
        order, coef = _sort_pairs(key, coef.reshape(-1), self.held + 1)
        counts = jnp.sum(
            key[:, None] == jnp.arange(self.held, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)
        return w, (order // k).astype(jnp.int32), coef, counts

    def _experts(self, params, state, a):
        cd = a.dtype
        with jax.named_scope("route"):
            w, rows, coef, counts = self._route(params, state, a)
        chunk = expert_chunk_rows(w.shape[0], self.conf.experts_per_token,
                                  self.conf.n_experts)
        gate = params.get("Wg")
        with jax.named_scope("experts"):
            y = grouped.expert_ffn(
                w.astype(cd), rows, coef, counts,
                None if gate is None else gate.astype(cd),
                params["Wu"].astype(cd), params["Wd"].astype(cd),
                chunk=chunk)
        if "Ws_u" in params:
            with jax.named_scope("shared_expert"):
                y = y + _feed_forward(
                    w.astype(cd), params.get("Ws_g"), params["Ws_u"],
                    params["Ws_d"])
        with jax.named_scope("route"):
            out = a + y.reshape(a.shape).astype(cd)
            low = state["expert_rows_total"][0] + counts
            total = jnp.stack([
                low & ((1 << _LIMB) - 1),
                state["expert_rows_total"][1] + (low >> _LIMB)])
        return out, {**state, "expert_rows": counts,
                     "expert_rows_total": total}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._experts(params, state, x.astype(self.compute_dtype))


class _GroupedQueryHeads:
    """The projections of a pre-norm grouped-query attention, for the
    layers whose conf has ``n_heads``, ``n_kv_heads`` and ``head_dim``:
    parameters ``attn_ln_g``, ``Wq``, ``Wk``, ``Wv``, ``Wo``."""

    def _check_heads(self):
        conf = self.conf
        if conf.n_heads % conf.n_kv_heads:
            raise ValueError(
                f"{type(conf).__name__} '{conf.name}': {conf.n_heads} query "
                f"heads cannot share {conf.n_kv_heads} key/value heads "
                "evenly")

    def _init_heads(self, kq, kk, kv, ko):
        d = int(self.conf.n_out)
        hq, hkv, dh = (int(self.conf.n_heads), int(self.conf.n_kv_heads),
                       int(self.conf.head_dim))
        return {
            "attn_ln_g": jnp.ones((d,), self.param_dtype),
            "Wq": self._init(kq, (d, hq * dh), d, hq * dh),
            "Wk": self._init(kk, (d, hkv * dh), d, hkv * dh),
            "Wv": self._init(kv, (d, hkv * dh), d, hkv * dh),
            "Wo": self._init(ko, (hq * dh, d), hq * dh, d),
        }

    def _normed(self, params, x):
        return _rms_norm(x, params["attn_ln_g"], self.conf.eps).astype(x.dtype)

    def _heads(self, params, x):
        """q [b, t, hq, dh], k and v [b, t, hkv, dh] of the normed rows,
        in ``x``'s dtype."""
        return self._projected_heads(params, self._normed(params, x))

    def _projected_heads(self, params, u):
        """``_heads`` of the normed rows ``u``."""
        conf, cd = self.conf, u.dtype
        b, t, _ = u.shape
        hq, hkv, dh = int(conf.n_heads), int(conf.n_kv_heads), int(
            conf.head_dim)
        return (_project(u, params["Wq"], cd).reshape(b, t, hq, dh),
                _project(u, params["Wk"], cd).reshape(b, t, hkv, dh),
                _project(u, params["Wv"], cd).reshape(b, t, hkv, dh))

    def _merge_heads(self, params, x, o):
        b, t, _ = x.shape
        return x + _project(o.reshape(b, t, -1), params["Wo"], x.dtype)


class _RotatedHeads(_GroupedQueryHeads):
    """``_GroupedQueryHeads`` with an RMS norm on every query and key
    head and rotary positions over the whole head, for the layers whose
    conf has ``rope_theta`` besides: parameters ``q_norm_g``,
    ``k_norm_g`` too."""

    def _init_rotated_heads(self, kq, kk, kv, ko):
        dh = int(self.conf.head_dim)
        return {**self._init_heads(kq, kk, kv, ko),
                "q_norm_g": jnp.ones((dh,), self.param_dtype),
                "k_norm_g": jnp.ones((dh,), self.param_dtype)}

    def _rotated_heads(self, params, x, pos):
        """``_heads`` with q and k normed and rotated, row ``i`` at
        ``pos[i]``."""
        return self._normed_rotated(params, *self._heads(params, x), pos)

    def _normed_rotated(self, params, q, k, v, pos):
        conf, cd = self.conf, q.dtype
        q = _rotate(_rms_norm(q, params["q_norm_g"], conf.eps),
                    conf.rope_theta, pos).astype(cd)
        k = _rotate(_rms_norm(k, params["k_norm_g"], conf.eps),
                    conf.rope_theta, pos).astype(cd)
        return q, k, v

    def _causal_attention(self, params, x):
        """``x + W_o attention(RMSNorm(x))`` of ``x`` [b, t, d] under the
        causal rule, rows at positions 0..t-1, under the scope
        ``attn``."""
        with jax.named_scope("attn"):
            q, k, v = self._rotated_heads(
                params, x, jnp.arange(x.shape[1], dtype=jnp.int32))
            with jax.named_scope("causal_attention"):
                o = att.causal_attention(q, k, v)
            return self._merge_heads(params, x, o)


class _RotatedHeadsMoeBlock(_RotatedHeads, RoutedExpertsLayer):
    """The parameters of ``_RotatedHeads`` beside the routed experts';
    the mask rule is the subclass's."""

    def __init__(self, conf, input_type, global_conf, policy):
        super().__init__(conf, input_type, global_conf, policy)
        self._check_heads()

    def init_params(self, key):
        k_experts, kq, kk, kv, ko = jax.random.split(key, 5)
        params = super().init_params(k_experts)
        params.update(self._init_rotated_heads(kq, kk, kv, ko))
        return params


class MoeDecoderBlockLayer(_RotatedHeadsMoeBlock):
    def _attention(self, params, x):
        t = x.shape[1]
        if t % 2:
            raise ValueError(
                f"MoeDecoderBlock '{self.name}' takes a noised and a clean "
                f"copy of each sequence, an even number of rows; got {t}")
        # both halves of the rows sit at 0..L-1
        q, k, v = self._rotated_heads(
            params, x, jnp.arange(t, dtype=jnp.int32) % (t // 2))
        with jax.named_scope("block_attention"):
            o = att.block_diffusion_mha(q, k, v, seq_len=t // 2,
                                        block_len=int(self.conf.block_len))
        return self._merge_heads(params, x, o)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("attn"):
            a = self._attention(params, x.astype(self.compute_dtype))
        return self._experts(params, state, a)


class SparseMoeBlockLayer(_RotatedHeadsMoeBlock):
    """``CausalMoeBlockLayer`` whose attention takes, for every row, the
    ``index_topk`` keys a learned indexer scores highest (nn/conf/
    layers_decoder.py ``SparseMoeBlock`` has the equations). Parameters
    beside the block's: ``W_IQ`` [d, nI dI], ``W_IK`` [d, dI],
    ``kI_ln_g`` and ``kI_ln_b`` [dI], ``W_w`` [d, nI].

    The indexer's loss (``ops/sparse_attention.py`` ``dsa_indexer_loss``)
    is a term of the net's loss that this layer makes: it rides in the
    new state under ``own_loss`` and ``MultiLayerNetwork._loss`` adds it.
    Its gradient reaches the indexer's five leaves alone, and the data
    loss's none of them: the indexer reads the normed rows without their
    gradient, and the selection has none. Beside it the state holds the
    last step's selected pairs (``dsa_selected_pairs``) and the tile
    pairs the attention kernels walked and skipped (``dsa_tiles``).

    With ``hands_back_selection`` set while a forward is traced, that
    forward's new state also carries the selection's words under
    ``selection``: a check reads what the layer chose on its own path.
    A training step is never traced with it set (8 MB a layer at 8,192
    rows)."""

    own_loss = "dsa_indexer_kl"
    hands_back_selection = False

    def init_params(self, key):
        k_block, kq, kk, kw = jax.random.split(key, 4)
        params = super().init_params(k_block)
        d = int(self.conf.n_out)
        n, di = int(self.conf.index_heads), int(self.conf.index_head_dim)
        params.update({
            "W_IQ": self._init(kq, (d, n * di), d, n * di),
            "W_IK": self._init(kk, (d, di), d, di),
            "kI_ln_g": jnp.ones((di,), self.param_dtype),
            "kI_ln_b": jnp.zeros((di,), self.param_dtype),
            "W_w": self._init(kw, (d, n), d, n),
        })
        return params

    def init_state(self):
        return {**super().init_state(),
                "dsa_indexer_kl": jnp.zeros((), jnp.float32),
                "dsa_selected_pairs": jnp.zeros((), jnp.int32),
                "dsa_tiles": jnp.zeros((2,), jnp.int32)}

    def _indexer(self, params, u, pos):
        """qI [b, t, nI, dI] and kI [b, t, dI] rotated, in ``u``'s dtype,
        and ``w`` [b, t, nI] float32, of the normed rows ``u`` without
        their gradient."""
        conf, cd = self.conf, u.dtype
        b, t, _ = u.shape
        n, di = int(conf.index_heads), int(conf.index_head_dim)
        u = jax.lax.stop_gradient(u)
        q_index = _rotate(_project(u, params["W_IQ"], cd).reshape(b, t, n, di)
                          .astype(jnp.float32), conf.rope_theta, pos)
        k_index = _layer_norm(_project(u, params["W_IK"], cd),
                              params["kI_ln_g"], params["kI_ln_b"], conf.eps)
        k_index = _rotate(k_index[:, :, None, :], conf.rope_theta,
                          pos)[:, :, 0]
        w = jnp.einsum("btf,fg->btg", u, params["W_w"].astype(cd),
                       preferred_element_type=jnp.float32) / math.sqrt(n * di)
        return q_index.astype(cd), k_index.astype(cd), w

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        conf = self.conf
        x = x.astype(self.compute_dtype)
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        with jax.named_scope("attn"):
            u = self._normed(params, x)
            q, k, v = self._normed_rotated(
                params, *self._projected_heads(params, u), pos)
            with jax.named_scope("dsa_indexer"):
                q_index, k_index, w = self._indexer(params, u, pos)
            with jax.named_scope("dsa_select"):
                sel, lse_index = sa.dsa_select(q_index, k_index, w,
                                               topk=int(conf.index_topk))
                pairs = sa.selected_pairs(sel)
            with jax.named_scope("sparse_attention"):
                o, lse, tiles = sa.sparse_attention(q, k, v, sel)
            with jax.named_scope("dsa_kl"):
                kl = sa.dsa_indexer_loss(q, k, lse, q_index, k_index, w, sel,
                                         lse_index)
            a = self._merge_heads(params, x, o)
        y, new_state = self._experts(params, state, a)
        new_state = {**new_state, "dsa_selected_pairs": pairs,
                     "dsa_tiles": tiles, "dsa_indexer_kl": kl}
        if self.hands_back_selection:
            new_state["selection"] = sel
        return y, new_state


class _DenseMlp:
    """The feed-forward of a leading dense layer, for the layers whose
    conf has ``mlp_width``: parameters ``ln_g``, ``Wg``, ``Wu``, ``Wd``;
    under the scope ``dense_mlp``."""

    def _init_mlp(self, kg, ku, kd):
        d, f = int(self.conf.n_out), int(self.conf.mlp_width)
        return {"ln_g": jnp.ones((d,), self.param_dtype),
                "Wg": self._init(kg, (d, f), d, f),
                "Wu": self._init(ku, (d, f), d, f),
                "Wd": self._init(kd, (f, d), f, d)}

    def _mlp(self, params, a):
        cd = a.dtype
        with jax.named_scope("dense_mlp"):
            w = _rms_norm(a, params["ln_g"], self.conf.eps).astype(cd)
            y = _feed_forward(w.reshape(-1, w.shape[-1]), params["Wg"],
                              params["Wu"], params["Wd"])
            return a + y.reshape(a.shape).astype(cd)


class CausalMoeBlockLayer(_RotatedHeadsMoeBlock):
    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._experts(params, state, self._causal_attention(
            params, x.astype(self.compute_dtype)))


class CausalDenseBlockLayer(_RotatedHeads, _DenseMlp, _DecoderLayer):
    def __init__(self, conf, input_type, global_conf, policy):
        super().__init__(conf, input_type, global_conf, policy)
        self._check_heads()

    def init_params(self, key):
        kq, kk, kv, ko, kg, ku, kd = jax.random.split(key, 7)
        return {**self._init_rotated_heads(kq, kk, kv, ko),
                **self._init_mlp(kg, ku, kd)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._mlp(params, self._causal_attention(
            params, x.astype(self.compute_dtype))), state


class _ShortConvOperator:
    """A pre-norm gated short-convolution operator (nn/conf/
    layers_decoder.py ``_ShortConv`` has the equations), for the layers
    whose conf has ``conv_kernel``: parameters ``op_ln_g``, ``W_in``
    ``[d, 3 d]`` (``B``, ``C``, ``x~`` in this order), ``conv_w`` ``[d,
    K]`` and ``W_out``."""

    def _init_operator(self, key):
        d, k = int(self.conf.n_out), int(self.conf.conv_kernel)
        k_in, k_w, k_out = jax.random.split(key, 3)
        # the filter as a depthwise Conv1d's default, as the state-space
        # mixer's: uniform(+-1/sqrt(kernel))
        bound = 1.0 / math.sqrt(k)
        return {
            "op_ln_g": jnp.ones((d,), self.param_dtype),
            "W_in": self._init(k_in, (d, 3 * d), d, 3 * d),
            "conv_w": jax.random.uniform(k_w, (d, k), self.param_dtype,
                                         -bound, bound),
            "W_out": self._init(k_out, (d, d), d, d),
        }

    def _operator(self, params, x):
        """``x + W_out (C * conv(B * x~))`` of ``x`` [b, t, d], under the
        scope ``conv_op``."""
        cd = x.dtype
        _count_short_conv_layer()
        with jax.named_scope("conv_op"):
            u = _rms_norm(x, params["op_ln_g"], self.conf.eps).astype(cd)
            bcx = _project(u, params["W_in"], cd)
            with jax.named_scope("short_conv"):
                y = shortconv.gated_short_conv(bcx, params["conv_w"])
            return x + _project(y, params["W_out"], cd)


def _count_short_conv_layer() -> None:
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        "dl4j_short_conv_layers_traced_total",
        "Short-convolution operator layers traced (forward walks of a "
        "net)").inc()


class ShortConvMoeBlockLayer(_ShortConvOperator, RoutedExpertsLayer):
    def init_params(self, key):
        k_experts, k_op = jax.random.split(key)
        params = super().init_params(k_experts)
        params.update(self._init_operator(k_op))
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._experts(params, state, self._operator(
            params, x.astype(self.compute_dtype)))


class ShortConvDenseBlockLayer(_ShortConvOperator, _DenseMlp, _DecoderLayer):
    def init_params(self, key):
        k_op, kg, ku, kd = jax.random.split(key, 4)
        return {**self._init_operator(k_op), **self._init_mlp(kg, ku, kd)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._mlp(params, self._operator(
            params, x.astype(self.compute_dtype))), state


class CausalAttentionLayer(_GroupedQueryHeads, _DecoderLayer):
    def __init__(self, conf, input_type, global_conf, policy):
        super().__init__(conf, input_type, global_conf, policy)
        self._check_heads()

    def init_params(self, key):
        return self._init_heads(*jax.random.split(key, 4))

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = x.astype(self.compute_dtype)
        with jax.named_scope("attn"):
            q, k, v = self._heads(params, x)
            with jax.named_scope("causal_attention"):
                o = att.causal_attention(q, k, v)
            return self._merge_heads(params, x, o), state


class _LatentHeads:
    """A pre-norm latent attention (multi-head, queries and keys/values
    each expanded from a compressed row), for the layers whose conf has
    ``n_heads``, ``q_rank``, ``kv_rank``, ``nope_dim``, ``rope_dim``,
    ``v_dim`` and ``rope_theta``: parameters ``attn_ln_g``, ``W_dq``,
    ``q_ln_g``, ``W_uq``, ``W_dkv``, ``kv_ln_g``, ``W_ukv``, ``Wo``
    (nn/conf/layers_decoder.py ``LatentMoeBlock`` has the equations).

    Every head has keys and values of its own, so ``causal_attention``
    sees a group of 1. This layer passes no tile: ops/attention.py reads
    the group from the operands (``_bd_query_tile``) and gives a group
    of 1 a query tile of 512 positions where the grouped-query layers'
    8 or 16 heads a key/value head get 128, about 1,024 rows a grid
    step either way, because a grid step's fixed cost is the same
    whatever it holds (PERF.md, Findings PR 38)."""

    def _check_latent(self):
        conf = self.conf
        if conf.v_dim != conf.nope_dim + conf.rope_dim:
            raise ValueError(
                f"{type(conf).__name__} '{conf.name}': values of "
                f"{conf.v_dim} beside queries and keys of {conf.nope_dim} + "
                f"{conf.rope_dim}: ops/attention.py takes one head size")
        if conf.rope_dim % 2:
            raise ValueError(
                f"{type(conf).__name__} '{conf.name}': the rotation pairs "
                f"columns, and {conf.rope_dim} is odd")

    def _init_latent(self, key):
        conf = self.conf
        d, h = int(conf.n_out), int(conf.n_heads)
        rq, rkv = int(conf.q_rank), int(conf.kv_rank)
        dn, dr, dv = int(conf.nope_dim), int(conf.rope_dim), int(conf.v_dim)
        k_dq, k_uq, k_dkv, k_ukv, k_o = jax.random.split(key, 5)
        pd = self.param_dtype
        return {
            "attn_ln_g": jnp.ones((d,), pd),
            "W_dq": self._init(k_dq, (d, rq), d, rq),
            "q_ln_g": jnp.ones((rq,), pd),
            "W_uq": self._init(k_uq, (rq, h * (dn + dr)), rq, h * (dn + dr)),
            "W_dkv": self._init(k_dkv, (d, rkv + dr), d, rkv + dr),
            "kv_ln_g": jnp.ones((rkv,), pd),
            "W_ukv": self._init(k_ukv, (rkv, h * (dn + dv)), rkv,
                                h * (dn + dv)),
            "Wo": self._init(k_o, (h * dv, d), h * dv, d),
        }

    def _latent_attention(self, params, x):
        """``x + W_o attention(RMSNorm(x))`` of ``x`` [b, t, d], rows at
        positions 0..t-1, under the scope ``attn``."""
        conf, cd = self.conf, x.dtype
        b, t, _ = x.shape
        h, rkv = int(conf.n_heads), int(conf.kv_rank)
        dn, dr = int(conf.nope_dim), int(conf.rope_dim)
        _count_latent_layer()
        with jax.named_scope("attn"):
            u = _rms_norm(x, params["attn_ln_g"], conf.eps).astype(cd)
            with jax.named_scope("mla_down"):
                c_q = _rms_norm(_project(u, params["W_dq"], cd),
                                params["q_ln_g"], conf.eps).astype(cd)
                c_kv, k_rope = jnp.split(_project(u, params["W_dkv"], cd),
                                         [rkv], axis=-1)
                c_kv = _rms_norm(c_kv, params["kv_ln_g"],
                                 conf.eps).astype(cd)
            with jax.named_scope("mla_up"):
                pos = jnp.arange(t, dtype=jnp.int32)
                q = _project(c_q, params["W_uq"], cd).reshape(
                    b, t, h, dn + dr)
                q = jnp.concatenate([q[..., :dn], _rotate(
                    q[..., dn:], conf.rope_theta, pos).astype(cd)], axis=-1)
                kv = _project(c_kv, params["W_ukv"], cd).reshape(b, t, h, -1)
                # one rotated key slice for all heads
                k_rope = _rotate(k_rope[:, :, None, :], conf.rope_theta,
                                 pos).astype(cd)
                k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                    k_rope, (b, t, h, dr))], axis=-1)
                v = kv[..., dn:]
            with jax.named_scope("causal_attention"):
                o = att.causal_attention(q, k, v)
            return x + _project(o.reshape(b, t, -1), params["Wo"], cd)


def _count_latent_layer() -> None:
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        "dl4j_mla_layers_traced_total",
        "Latent-attention layers traced (forward walks of a net)").inc()


class LatentMoeBlockLayer(_LatentHeads, RoutedExpertsLayer):
    def __init__(self, conf, input_type, global_conf, policy):
        super().__init__(conf, input_type, global_conf, policy)
        self._check_latent()

    def init_params(self, key):
        k_experts, k_attn = jax.random.split(key)
        params = super().init_params(k_experts)
        params.update(self._init_latent(k_attn))
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._experts(params, state, self._latent_attention(
            params, x.astype(self.compute_dtype)))


class LatentDenseBlockLayer(_LatentHeads, _DenseMlp, _DecoderLayer):
    def __init__(self, conf, input_type, global_conf, policy):
        super().__init__(conf, input_type, global_conf, policy)
        self._check_latent()

    def init_params(self, key):
        k_attn, kg, ku, kd = jax.random.split(key, 4)
        return {**self._init_latent(k_attn), **self._init_mlp(kg, ku, kd)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._mlp(params, self._latent_attention(
            params, x.astype(self.compute_dtype))), state


def _inverse_softplus(dt):
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2MixerLayer(_DecoderLayer):
    def __init__(self, conf, input_type, global_conf, policy):
        super().__init__(conf, input_type, global_conf, policy)
        if conf.n_heads % conf.n_groups:
            raise ValueError(
                f"Mamba2Mixer '{conf.name}': {conf.n_heads} heads cannot "
                f"share {conf.n_groups} groups evenly")
        self.inner = int(conf.n_heads) * int(conf.head_dim)
        self.bc = int(conf.n_groups) * int(conf.state_size)

    def init_params(self, key):
        conf = self.conf
        d, heads, k = int(conf.n_out), int(conf.n_heads), int(
            conf.conv_kernel)
        wide = 2 * self.inner + 2 * self.bc + heads
        conv = self.inner + 2 * self.bc
        k_in, k_out, k_w, k_b, k_dt = jax.random.split(key, 5)
        pd = self.param_dtype
        # dt ~ logU[dt_min, dt_max], floored; the convolution as a
        # depthwise Conv1d's default, uniform(+-1/sqrt(kernel))
        dt = jnp.exp(jax.random.uniform(k_dt, (heads,), jnp.float32)
                     * (math.log(conf.dt_max) - math.log(conf.dt_min))
                     + math.log(conf.dt_min))
        bound = 1.0 / math.sqrt(k)
        return {
            "ln_g": jnp.ones((d,), pd),
            "W_in": self._init(k_in, (d, wide), d, wide),
            "conv_w": jax.random.uniform(k_w, (conv, k), pd, -bound, bound),
            "conv_b": jax.random.uniform(k_b, (conv,), pd, -bound, bound),
            "dt_bias": _inverse_softplus(
                jnp.maximum(dt, conf.dt_floor)).astype(pd),
            "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=pd)),
            "D": jnp.ones((heads,), pd),
            "norm_g": jnp.ones((self.inner,), pd),
            "W_out": self._init(k_out, (self.inner, d), self.inner, d),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        conf, cd = self.conf, self.compute_dtype
        x = x.astype(cd)
        b, t, _ = x.shape
        heads, groups = int(conf.n_heads), int(conf.n_groups)
        f32 = jnp.float32
        with jax.named_scope("mamba"):
            u = _rms_norm(x, params["ln_g"], conf.eps).astype(cd)
            zxbcdt = _project(u, params["W_in"], cd)
            z, xbc, dt = jnp.split(
                zxbcdt, [self.inner, 2 * self.inner + 2 * self.bc], axis=-1)
            with jax.named_scope("ssm_conv"):
                xbc = ssm.causal_conv1d(xbc, params["conv_w"],
                                        params["conv_b"])
            xs, bm, cm = jnp.split(xbc, [self.inner, self.inner + self.bc],
                                   axis=-1)
            dt = jax.nn.softplus(dt.astype(f32)
                                 + params["dt_bias"].astype(f32))
            with jax.named_scope("ssm_scan"):
                y = ssm.ssm_scan(
                    xs.reshape(b, t, heads, -1), dt,
                    -jnp.exp(params["A_log"].astype(f32)),
                    bm.reshape(b, t, groups, -1),
                    cm.reshape(b, t, groups, -1),
                    params["D"].astype(f32), chunk=int(conf.chunk))
            with jax.named_scope("ssm_norm"):
                y = ssm.gated_group_norm(y.reshape(b, t, -1), z,
                                         params["norm_g"], groups, conf.eps)
            return x + _project(y.astype(cd), params["W_out"], cd), state


class TokenOutputLayer(_DecoderLayer):
    @property
    def shares(self):
        """A tied head reads the embedding's matrix under the local name
        ``Emb`` and stores none (nn/multilayer.py ``_layer_params``):
        autodiff sums the product's gradient into the gather's."""
        tied = self.conf.tied_to
        return {"Emb": (tied, "W")} if tied else {}

    def init_params(self, key):
        if self.conf.tied_to:
            return {}
        n_in, n_out = int(self.conf.n_in), int(self.conf.n_out)
        return {"W": self._init(key, (n_in, n_out), n_in, n_out)}

    def _logits(self, params, x):
        cd = self.compute_dtype
        rows = x if self.conf.causal else x[:, :x.shape[1] // 2]
        if self.conf.tied_to:
            emb = params["Emb"]
            if emb.shape != (int(self.conf.n_out), int(self.conf.n_in)):
                raise ValueError(
                    f"TokenOutput '{self.name}' is tied to "
                    f"'{self.conf.tied_to}', whose matrix is "
                    f"{tuple(emb.shape)}; logits of {self.conf.n_out} over "
                    f"rows of {self.conf.n_in} need its transpose")
            return jnp.einsum("btf,gf->btg", rows.astype(cd), emb.astype(cd),
                              preferred_element_type=jnp.float32)
        return jnp.einsum("btf,fg->btg", rows.astype(cd),
                          params["W"].astype(cd),
                          preferred_element_type=jnp.float32)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.activation_fn(self._logits(params, x)), state

    def loss(self, params, x, labels, *, train=False, rng=None, mask=None):
        logits = self._logits(params, x)                 # float32 [b, L, V]
        if not jnp.issubdtype(labels.dtype, jnp.integer):
            raise TypeError(
                f"TokenOutput '{self.name}' takes integer labels [b, t], "
                f"got {labels.dtype}{tuple(labels.shape)}")
        return _token_nll(logits, labels, mask)


def _token_nll(logits, labels, mask):
    """The cross-entropy of ``TokenOutput``: summed, over the number of
    labels."""
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0])
    if mask is not None:
        nll = nll * mask.astype(jnp.float32)
    return jnp.sum(nll) / math.prod(labels.shape)


class MtpTokenOutputLayer(LatentMoeBlockLayer):
    """The model's final norm and head, and one multi-token-prediction
    module that shares the head and the embedding (nn/conf/
    layers_decoder.py ``MtpTokenOutput``). The block this class inherits
    is the module's own expert layer; its routing counts ride in this
    layer's state as any expert layer's do, and beside them
    ``mtp_loss``, float32 [2]: the last step's two losses, unweighted."""

    loss_uses_state = True
    loss_returns_state = True

    @property
    def shares(self):
        """Leaves of other layers that this one reads: a net hands them
        over under the local name, and their gradient is summed into the
        owner's (nn/multilayer.py ``_layer_params``)."""
        return {"Emb": (self.conf.embedding, "W")}

    def init_params(self, key):
        d, vocab = int(self.conf.n_out), int(self.conf.vocab_size)
        k_block, k_head, k_eh = jax.random.split(key, 3)
        params = super().init_params(k_block)
        params.update({
            "W": self._init(k_head, (d, vocab), d, vocab),
            "W_eh": self._init(k_eh, (2 * d, d), 2 * d, d),
        })
        # a buffer each: the step donates every leaf
        for norm in ("norm_g", "enorm_g", "hnorm_g", "mtp_norm_g"):
            params[norm] = jnp.ones((d,), self.param_dtype)
        return params

    def init_state(self):
        return {**super().init_state(),
                "mtp_loss": jnp.zeros((2,), jnp.float32)}

    def _logits(self, params, norm, x):
        cd = self.compute_dtype
        rows = _rms_norm(x, params[norm], self.conf.eps).astype(cd)
        return jnp.einsum("btf,fg->btg", rows, params["W"].astype(cd),
                          preferred_element_type=jnp.float32)

    def module(self, params, state, h, next_ids):
        """The module's expert layer on ``W_eh [RMSNorm(Emb(next_ids)) ;
        RMSNorm(h)]``: (its input, its output, the layer's new state)."""
        conf, cd = self.conf, self.compute_dtype
        e = jnp.take(params["Emb"], next_ids, axis=0)
        both = jnp.concatenate([
            _rms_norm(e, params["enorm_g"], conf.eps),
            _rms_norm(h, params["hnorm_g"], conf.eps)], axis=-1)
        given = _project(both, params["W_eh"], cd)
        g, new_state = super().apply(params, state, given)
        return given, g, new_state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("head"):
            return self.activation_fn(self._logits(params, "norm_g", x)), state

    def loss(self, params, x, labels, *, train=False, rng=None, mask=None,
             state=None):
        if (not jnp.issubdtype(labels.dtype, jnp.integer)
                or labels.ndim != 3 or labels.shape[1] != 2):
            raise TypeError(
                f"MtpTokenOutput '{self.name}' takes integer labels "
                f"[b, 2, t], the next token and the one after it; got "
                f"{labels.dtype}{tuple(labels.shape)}")
        first, second = labels[:, 0], labels[:, 1]
        masks = (None, None) if mask is None else (mask[:, 0], mask[:, 1])
        with jax.named_scope("head"):
            main = _token_nll(self._logits(params, "norm_g", x), first,
                              masks[0])
        with jax.named_scope("mtp"):
            _, g, new_state = self.module(params, state, x, first)
            ahead = _token_nll(self._logits(params, "mtp_norm_g", g), second,
                               masks[1])
        new_state["mtp_loss"] = jnp.stack([main, ahead])
        return main + float(self.conf.mtp_weight) * ahead, new_state
