"""``RoutedExpertsLayer._route``: the pairs' weights ride the sort, and
their gradient is sorted back (nn/layers/decoder.py ``_sort_pairs``).

The plain formula is kept here: top-k, ``take_along_axis``, ``argsort``
and ``coef[order]``, a gather of every (row, choice) whose transpose is
a scatter-add of as many single scalars. The layer's route must give its
bits, forward and backward, at the routing shapes of the four decoders
(``k``, the router's width, the experts held and the router as
published; few rows of a small width), and the layer's gradient must
hold no gather or scatter-add of that kind.

Bit for bit means primitive by primitive, so the comparison runs
outside ``jit``: inside one program XLA fuses the one-hot sums with the
softmax or the division next to them where a gather stood alone, and the
CPU's fused loops round the last bit of a score differently (2e-7 of
the largest entry; the jitted comparison below allows 1e-6).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (
    MultiLayerNetwork, NeuralNetConfiguration, zoo)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers_decoder import (
    RoutedExperts, TokenOutput)
from deeplearning4j_tpu.nn.layers import decoder
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.ops import grouped

D = 32
# (rows, k, n_experts, held, first_expert) and the router's fields, by
# the configuration they are the routing of (PERF.md section 4)
ROUTINGS = {
    "sdar_30b_a3b": (64, 8, 128, 16, 32, dict(router="softmax")),
    "nemotron3_nano_30b_a3b": (48, 6, 128, 8, 120, dict(
        router="sigmoid", routed_scale=2.5, expert_form="relu2",
        shared_width=16)),
    "glm4_7_flash": (48, 4, 64, 8, 0, dict(
        router="sigmoid", routed_scale=1.8, shared_width=16)),
    "lfm2_24b_a2b": (64, 4, 64, 8, 24, dict(
        router="sigmoid", router_eps=1e-6)),
}


def _layer(k, n_experts, held, first, fields, d=D, f=24, seed=5):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .dtype(zoo.F32)
            .weight_init({"type": "normal", "mean": 0.0, "std": 0.3}).list()
            .layer(RoutedExperts(
                n_out=d, n_experts=n_experts, experts_per_token=k,
                expert_width=f, experts_held=held, first_expert=first,
                **fields))
            .layer(TokenOutput(n_out=8))
            .set_input_type(InputType.recurrent(d)).build())
    net = MultiLayerNetwork(conf).init()
    return net.layers[0], net.params["layer_0"], net.state["layer_0"]


def _plain_route(layer, params, state, a):
    """``_route`` as it was written before the weights rode the sort."""
    conf = layer.conf
    k = int(conf.experts_per_token)
    w = decoder._rms_norm(a, params["ln_g"], conf.eps).reshape(
        -1, a.shape[-1])
    logits = jnp.dot(w, params["Wr"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if conf.router == "softmax":
        top, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        coef = top / jnp.sum(top, axis=-1, keepdims=True)
    else:
        score = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(
            score + jax.lax.stop_gradient(state["router_bias"]), k)
        top = jnp.take_along_axis(score, chosen, axis=-1)
        coef = top / (jnp.sum(top, axis=-1, keepdims=True)
                      + float(conf.router_eps))
    if conf.routed_scale != 1.0:
        coef = coef * float(conf.routed_scale)
    local = chosen.astype(jnp.int32) - int(conf.first_expert)
    key = jnp.where((local >= 0) & (local < layer.held), local,
                    layer.held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    counts = jnp.sum(
        key[:, None] == jnp.arange(layer.held, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    return (w, (order // k).astype(jnp.int32), coef.reshape(-1)[order],
            counts)


@pytest.mark.parametrize("name", list(ROUTINGS))
def test_route_and_its_gradient_are_the_plain_formulas_bits(name):
    rows, k, n_experts, held, first, fields = ROUTINGS[name]
    layer, params, state = _layer(k, n_experts, held, first, fields)
    # rows with one positive column, through which one held expert is
    # made what no row wants
    a = jax.random.normal(jax.random.PRNGKey(2), (1, rows, D), jnp.float32)
    a = a.at[..., 0].set(2.0)
    empty = 3
    params = {**params, "Wr": params["Wr"].at[0].set(0.0).at[
        0, first + empty].set(-20.0)}
    if "router_bias" in state:      # the bias moves the choice
        state = {**state, "router_bias": 0.05 * jax.random.normal(
            jax.random.PRNGKey(7), state["router_bias"].shape)}
    g_w = jax.random.normal(jax.random.PRNGKey(3), (rows, D))
    g_coef = jax.random.normal(jax.random.PRNGKey(4), (rows * k,))

    def run(route, wrap):
        def loss(wr, ln_g, a):
            w, pair_rows, coef, counts = route(
                layer, {**params, "Wr": wr, "ln_g": ln_g}, state, a)
            return (jnp.sum(w * g_w) + jnp.sum(coef * g_coef),
                    (w, pair_rows, coef, counts))
        return wrap(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
            params["Wr"], params["ln_g"], a)

    (got, got_out), got_grads = run(type(layer)._route, lambda f: f)
    (want, want_out), want_grads = run(_plain_route, lambda f: f)
    for x, y in zip(got_out + got_grads, want_out + want_grads):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert float(got) == float(want)
    assert all(np.asarray(x).any() for x in got_grads)
    # one program: the same routing, and values to a fused loop's rounding
    (_, jit_out), jit_grads = run(type(layer)._route, jax.jit)
    for x, y in zip(jit_out + jit_grads, want_out + want_grads):
        if jnp.issubdtype(x.dtype, jnp.integer):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            assert np.abs(np.asarray(x) - np.asarray(y)).max() <= (
                1e-6 * np.abs(np.asarray(y)).max())
    # what the case is meant to hold: ties in the key (an expert with
    # several pairs, and the pairs held elsewhere), an expert with no
    # pair, a row none of whose choices is held here
    _, pair_rows, _, counts = (np.asarray(x) for x in got_out)
    assert counts[empty] == 0 and counts.max() > 1
    assert 0 < counts.sum() < rows * k
    assert len(set(pair_rows[:counts.sum()])) < rows


@pytest.mark.parametrize("form,operands", [("one int32 a pair", 2),
                                           ("three operands", 3)])
def test_sort_pairs_is_argsort_and_a_gather_to_the_bit(form, operands):
    """Both forms of the sort: the key and the place packed into one
    int32, and, where they would not fit, the stable sort of three."""
    pairs, held = 1000, 5
    n_keys = held + 1 if operands == 2 else 2 ** 31 // pairs + 1
    rng = np.random.default_rng(0)
    key = jnp.asarray(rng.integers(0, held + 1, pairs), jnp.int32)
    coef = jnp.asarray(rng.random(pairs), jnp.float32)
    g = jnp.asarray(rng.standard_normal(pairs), jnp.float32)

    def new(coef):
        order, held_coef = decoder._sort_pairs(key, coef, n_keys)
        return jnp.sum(held_coef * g), (order, held_coef)

    def plain(coef):
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        return jnp.sum(coef[order] * g), (order, coef[order])

    got = jax.jit(jax.value_and_grad(new, has_aux=True))(coef)
    want = jax.jit(jax.value_and_grad(plain, has_aux=True))(coef)
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    sorts = [eqn for eqn in _eqns(jax.make_jaxpr(jax.grad(
        lambda c: new(c)[0]))(coef).jaxpr) if eqn.primitive.name == "sort"]
    assert [len(eqn.invars) for eqn in sorts] == [operands, 2]


def _eqns(jaxpr):
    """Every equation of a program, sub-programs included (the bodies of
    loops and conditionals, kernels, what a ``custom_vjp`` became)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _scalar_moves(jaxpr):
    """``(primitive, indices)`` of every gather and scatter that moves
    single scalars, and the slice lengths of those that move more."""
    scalars, slices = [], set()
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "gather":
            indices, moved = eqn.invars[1].aval, eqn.outvars[0].aval
        elif eqn.primitive.name.startswith("scatter"):
            indices, moved = eqn.invars[1].aval, eqn.invars[2].aval
        else:
            continue
        n = math.prod(indices.shape[:-1])
        each = math.prod(moved.shape) // max(n, 1)
        if each == 1:
            scalars.append((eqn.primitive.name, n))
        else:
            slices.add((eqn.primitive.name, each))
    return scalars, slices


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
@pytest.mark.parametrize("executor,d,f", [("xla_chunks", D, 24),
                                          ("pallas", 128, 128)])
def test_the_layers_gradient_moves_no_pair_by_index(monkeypatch, router,
                                                    executor, d, f):
    """No gather and no scatter-add of ``rows * k`` single scalars, in
    the layer's gradient or in any program inside it; the rows
    ``ops/grouped.py`` moves are slices of ``d`` elements."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET",
                       "1" if executor == "pallas" else "0")
    rows, k, n_experts, held = 64, 4, 16, 4
    layer, params, state = _layer(k, n_experts, held, 4,
                                  dict(router=router), d=d, f=f)
    a = jax.random.normal(jax.random.PRNGKey(2), (1, rows, d), jnp.float32)
    assert grouped.grouped_supported(
        a[0], params["Wg"], params["Wu"], params["Wd"], rows * k,
        decoder.expert_chunk_rows(rows, k, n_experts)) == (
            executor == "pallas")

    def loss(params, a):
        return jnp.sum(layer.apply(params, state, a)[0] ** 2)

    program = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, a)
    scalars, slices = _scalar_moves(program.jaxpr)
    assert [m for m in scalars if m[1] >= rows * k] == []
    # the walk reached the executor's row gathers
    assert ("gather", d) in slices
    names = {eqn.primitive.name for eqn in _eqns(program.jaxpr)}
    assert "sort" in names and (
        ("pallas_call" in names) == (executor == "pallas"))

    # the plain formula is what the rule refuses
    def plain(params, a):
        return jnp.sum(_plain_route(layer, params, state, a)[2] ** 2)

    found, _ = _scalar_moves(
        jax.make_jaxpr(jax.grad(plain, (0, 1)))(params, a).jaxpr)
    assert ("gather", rows * k) in found
    assert any(name.startswith("scatter") and n == rows * k
               for name, n in found)
