"""Jaxpr hazard lint: trace the real jitted programs, walk the IR.

PR 6 (precision) and PR 13 (transformers) each hand-fixed the same
class of bug once: a silent f32 matmul inside a bf16 policy, a Python
scalar baked into a trace forcing a recompile per step, a fit step that
re-allocated its parameter buffers because ``donate_argnums`` was
dropped. This pass makes those one-off fixes a standing check: it
builds the *production* jitted callables — ``net._build_train_step()``
and the serving ``_get_apply`` forward — for both net classes and the
zoo models (incl. ``gpt_mini``), traces them on tiny dummy batches
(host-only: ``make_jaxpr`` / ``lower``, never ``compile``), and walks
the closed jaxpr recursively (into scan/while/pjit sub-jaxprs) for:

- **DL4J-J001** — a ``dot_general``/``conv_general_dilated`` producing
  float32 under a half-precision compute policy: the matmul the policy
  was supposed to run in bf16/f16 silently upcast.
- **DL4J-J002** — any float64 value in the jaxpr: an x64 weak-type
  promotion that doubles memory and voids cross-backend bit-identity.
- **DL4J-J003** — retrace bomb: lowering the same callable twice with
  value-varied (shape-identical) arguments yields different StableHLO,
  i.e. some input value was baked into the trace as a constant and
  every new value will pay a fresh trace+compile.
- **DL4J-J004** — donation miss: a fit step whose lowering carries no
  buffer-donation markers re-allocates params/opt_state every step.
- **DL4J-J005** — a primitive outside the determinism allowlist below,
  which would void the bit-identity contract (IDENTITY.md).

Findings are :class:`~deeplearning4j_tpu.analysis.Finding`s with
``path="<jaxpr>"`` and ``symbol=<target name>``; targets that fail to
build at all surface as **DL4J-J000** rather than a silent skip.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from deeplearning4j_tpu.analysis import Finding

__all__ = ["list_targets", "lint_target", "lint_all",
            "DETERMINISM_ALLOWLIST"]

#: Primitives the bit-identity contract trusts: shipped models must not
#: stray outside this set without an explicit review (grow it in the
#: same PR that introduces the new op, with an IDENTITY.md note).
DETERMINISM_ALLOWLIST = frozenset({
    # structure / data movement
    "add_any", "broadcast_in_dim", "concatenate", "convert_element_type",
    "copy", "device_put", "dynamic_slice", "dynamic_update_slice",
    "gather", "iota", "pad", "reshape", "rev", "scatter", "scatter-add",
    "scatter_add", "select_n", "slice", "squeeze", "transpose",
    # control flow / staging
    "closed_call", "cond", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "jit", "remat", "remat2", "scan", "while",
    # elementwise math
    "abs", "add", "and", "cbrt", "ceil", "clamp", "cos", "cosh", "div",
    "eq", "erf", "exp", "expm1", "floor", "ge", "gt", "integer_pow",
    "is_finite", "le", "log", "log1p", "logistic", "lt", "max", "min",
    "mul", "ne", "neg", "not", "or", "pow", "rem", "round", "rsqrt",
    "sign", "sin", "sinh", "sqrt", "square", "stop_gradient", "sub",
    "tan", "tanh", "xor",
    # reductions / linalg / windows (XLA lowers these without atomics —
    # the pooling fwd/bwd pair is bit-stable across runs)
    "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax", "dot_general",
    "conv_general_dilated", "reduce_and", "reduce_max", "reduce_min",
    "reduce_or", "reduce_precision", "reduce_prod", "reduce_sum",
    "reduce_window_max", "reduce_window_min", "reduce_window_sum",
    "select_and_scatter_add", "sort",
    # RNG (threefry is the deterministic counter-based generator)
    "random_bits", "random_fold_in", "random_seed", "random_split",
    "random_unwrap", "random_wrap", "threefry2x32",
    # collectives (deterministic reductions on a fixed mesh)
    "all_gather", "all_to_all", "ppermute", "psum", "pmax", "pmin",
})

_HALF_DTYPES = ("bfloat16", "float16")
_MATMUL_PRIMS = ("dot_general", "conv_general_dilated")


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    """Yield every eqn in a (closed) jaxpr, recursing into sub-jaxprs
    carried in eqn params (pjit/scan/while/cond/custom_vjp...)."""
    import jax.extend.core as jcore

    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                    yield from _iter_eqns(sub)


def _check_ir(closed, target: str, compute_dtype: str) -> List[Finding]:
    """J001 + J002 + J005 over one traced program (deduped messages)."""
    findings: Dict[str, Finding] = {}

    def emit(code, message):
        f = Finding(code=code, path="<jaxpr>", line=0, symbol=target,
                    message=message)
        findings.setdefault(f.fingerprint(), f)

    for eqn in _iter_eqns(closed):
        prim = eqn.primitive.name
        out_dtypes = {str(getattr(v.aval, "dtype", ""))
                      for v in eqn.outvars if hasattr(v, "aval")}
        if compute_dtype in _HALF_DTYPES and prim in _MATMUL_PRIMS \
                and "float32" in out_dtypes:
            emit("DL4J-J001",
                 f"{prim} produces float32 under a {compute_dtype} "
                 "compute policy")
        if "float64" in out_dtypes:
            emit("DL4J-J002", f"{prim} produces float64 (x64 weak-type "
                              "promotion)")
        if prim not in DETERMINISM_ALLOWLIST:
            emit("DL4J-J005",
                 f"primitive '{prim}' outside the determinism allowlist")
    return list(findings.values())


def _check_retrace(text_a: str, text_b: str, target: str) -> List[Finding]:
    """J003: two lowerings with value-varied, shape-identical args must
    produce identical StableHLO — a diff means a value got baked in."""
    if text_a != text_b:
        return [Finding(
            code="DL4J-J003", path="<jaxpr>", line=0, symbol=target,
            message="lowering differs between value-varied calls of the "
                    "same shape (a Python scalar/const is baked into the "
                    "trace; every new value retraces)")]
    return []


def _check_donation(lowered_text: str, target: str) -> List[Finding]:
    """J004: a fit step's lowering must carry buffer-donation markers
    for the params/opt_state operands."""
    if "tf.aliasing_output" in lowered_text \
            or "jax.buffer_donor" in lowered_text:
        return []
    return [Finding(
        code="DL4J-J004", path="<jaxpr>", line=0, symbol=target,
        message="no buffer-donation markers in the step lowering "
                "(donate_argnums dropped: params/opt_state re-allocate "
                "every step)")]


# --------------------------------------------------------------------------
# targets: the production jitted programs, on tiny dummy batches
# --------------------------------------------------------------------------

def _fit_args(net, variant: int, row=None, label_row=None):
    """Dummy fit-step args mirroring fit_batch's dispatch, with every
    *value* varied by ``variant`` while shapes/dtypes stay fixed (the
    J003 probe needs two such sets). ``row``/``label_row`` override the
    server-side shape inference (sequence models have no fixed length
    to infer)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.compilecache.precompile import (
        _dummy_batch, _infer_row_shapes)

    row_shapes = [row] if row is not None else _infer_row_shapes(net)
    if row_shapes is None:
        raise ValueError(f"cannot infer input shapes for {type(net)}")
    batch = _dummy_batch(net, 2, row_shapes,
                         None if label_row is None else [label_row],
                         fill=float(variant) * 0.25)
    return (net.params, net.state, net.opt_state,
            jnp.asarray(variant, jnp.int32), *net._batch_args(batch),
            jax.random.PRNGKey(variant))


def _forward_args(net, variant: int, row=None):
    import jax.numpy as jnp
    from deeplearning4j_tpu.compilecache.precompile import _infer_row_shapes

    row_shapes = [row] if row is not None else _infer_row_shapes(net)
    x = jnp.full((2,) + tuple(row_shapes[0]), float(variant), jnp.float32)
    return (net.params, net.state, x, None, None)


def _tiny_mlp():
    from deeplearning4j_tpu.zoo import models as zoo
    return zoo.mnist_mlp()


def _tiny_gpt():
    from deeplearning4j_tpu.zoo import models as zoo
    return zoo.gpt_mini(vocab_size=11, width=16, n_layers=2, n_heads=2,
                        max_len=8)


def _tiny_lenet():
    from deeplearning4j_tpu.zoo import models as zoo
    return zoo.lenet()


def _tiny_graph():
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import Dense, Output
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.updater import Adam

    conf = (NeuralNetConfiguration.builder()
            .seed(7).updater(Adam(1e-3)).graph_builder()
            .add_inputs("in")
            .add_layer("d1", Dense(n_out=6, activation="tanh"), "in")
            .add_layer("out", Output(n_out=3, activation="softmax",
                                     loss="mcxent"), "d1")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5))
            .build())
    return ComputationGraph(conf).init()


def _target(make_net: Callable, kind: str, row=None, label_row=None):
    """-> (jit_fn, args_a, args_b, compute_dtype, check_donation)."""
    net = make_net()
    compute = net.conf.global_conf.dtype.compute_dtype
    if kind == "fit":
        return (net._build_train_step(), _fit_args(net, 0, row, label_row),
                _fit_args(net, 1, row, label_row), compute, True)
    return (net._get_apply(collect=False, train=False),
            _forward_args(net, 0, row), _forward_args(net, 1, row),
            compute, False)


#: target name -> zero-arg builder (kept lazy: building traces a model)
TARGETS: Dict[str, Callable] = {
    "mnist_mlp.fit_step": lambda: _target(_tiny_mlp, "fit"),
    "mnist_mlp.forward": lambda: _target(_tiny_mlp, "forward"),
    "lenet.fit_step": lambda: _target(_tiny_lenet, "fit"),
    # one-hot token rows (T=8, V=11): the sequence length is a serving
    # choice, not inferable from the conf
    "gpt_mini.fit_step": lambda: _target(_tiny_gpt, "fit", row=(8, 11),
                                         label_row=(8, 11)),
    "gpt_mini.forward": lambda: _target(_tiny_gpt, "forward", row=(8, 11)),
    "graph.fit_step": lambda: _target(_tiny_graph, "fit"),
}


def list_targets() -> List[str]:
    return sorted(TARGETS)


def lint_target(name: str) -> List[Finding]:
    """All jaxpr checks for one named target. A target that fails to
    build/trace is itself a finding (J000), never a silent skip."""
    import jax

    try:
        jit_fn, args_a, args_b, compute, want_donation = TARGETS[name]()
        closed = jax.make_jaxpr(jit_fn)(*args_a)
        findings = _check_ir(closed, name, compute)
        lowered_a = jit_fn.lower(*args_a).as_text()
        lowered_b = jit_fn.lower(*args_b).as_text()
        findings.extend(_check_retrace(lowered_a, lowered_b, name))
        if want_donation:
            findings.extend(_check_donation(lowered_a, name))
        return findings
    except Exception as e:  # noqa: BLE001 — any failure is a finding
        return [Finding(
            code="DL4J-J000", path="<jaxpr>", line=0, symbol=name,
            message=f"target failed to trace: {type(e).__name__}: {e}")]


def lint_all(names: Optional[List[str]] = None) -> List[Finding]:
    out: List[Finding] = []
    for name in (names or list_targets()):
        out.extend(lint_target(name))
    return out
