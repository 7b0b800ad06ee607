"""Mixed-precision step runtime: loss scaling + the shared fused-step
builder (PRECISION.md).

The dtype *policy* lives in nn/conf/core.py (DtypePolicy: param/compute
dtypes + per-path overrides); the layers honor it at their forward
boundaries (cast activations to compute dtype at entry, accumulate
reductions in param dtype). What remains is the training-step discipline
of Micikevicius et al.'s mixed-precision recipe, implemented here so
MultiLayerNetwork and ComputationGraph share one step body:

- **No scaling (f32/bf16 policies):** ``build_step_fn`` traces exactly
  the seed step — value_and_grad over the loss, normalize + update —
  so default paths stay bit-identical.
- **Loss scaling (f16, or an explicit ``loss_scale``):** the loss is
  multiplied by the current scale before autodiff (lifting small
  gradients above f16's underflow floor), gradients are unscaled in the
  master dtype, and a step whose gradients contain any inf/nan is
  SKIPPED — params and optimizer slots are selected back to their old
  values bit-identically — while the scale backs off by
  ``1/loss_scale_factor``. After ``loss_scale_growth_interval``
  consecutive finite steps the scale regrows by ``loss_scale_factor``.

The scale state rides INSIDE ``opt_state`` under :data:`LOSS_SCALE_KEY`
(a reserved top-level key next to the per-layer slots). That placement
is load-bearing: the state is then carried through ``jax.jit`` donation,
``lax.scan`` multi-step chunking (nn/multistep.py), mesh sharding, and
orbax checkpoints with zero extra plumbing — a resumed or rolled-back
run (resilience/supervisor.py) restores the scale alongside the slots
it protected. ``apply_layer_updates`` iterates layers by name, so the
extra key passes through it untouched.

The skip-step contract composes with the resilience NaN sentinel rather
than double-firing it: the reported score is the TRUE (unscaled) loss,
so a gradient overflow with a finite loss skips silently here and never
looks like divergence to the supervisor; only a genuinely non-finite
loss still triggers its rollback — by which point this step has already
refused to poison the parameters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.updater import apply_layer_updates
from deeplearning4j_tpu.observability import opindex

#: reserved top-level opt_state key holding {"scale", "good_steps"}
LOSS_SCALE_KEY = "_loss_scale"

#: dynamic-scale ceiling: unbounded growth would eventually overflow the
#: scale itself to inf, after which backoff (inf/2 == inf) can never
#: recover; 2^24 clears any realistic gradient magnitude by orders of
#: magnitude while staying far from f32's exponent limit
_SCALE_MAX = 2.0 ** 24


def init_loss_scale_state(policy):
    """The opt_state subtree for ``policy``, or None when the policy
    needs no scaling. Called inside each net's ``init_trees`` so
    ``jax.eval_shape`` structure-only inits (clone/checkpoint-restore)
    see the same tree."""
    mode = policy.loss_scale_mode()
    if mode is None:
        return None
    init = policy.loss_scale_init if mode == "dynamic" else float(mode)
    return {"scale": jnp.asarray(init, jnp.float32),
            "good_steps": jnp.zeros((), jnp.int32)}


def all_finite(tree):
    """Scalar bool: every leaf of ``tree`` is free of inf/nan (the
    skip-step predicate, evaluated on the unscaled gradients)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.asarray(True)
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(l)) for l in leaves]))


def _next_scale_state(ls, finite, mode, policy):
    """Deterministic scale transition. Static mode only tracks
    good_steps (the scale is pinned); dynamic mode backs off on a
    skipped step and regrows after the growth interval."""
    good = jnp.where(finite, ls["good_steps"] + 1, 0)
    if mode != "dynamic":
        return {"scale": ls["scale"], "good_steps": good}
    factor = policy.loss_scale_factor
    grow = good >= policy.loss_scale_growth_interval
    scale = jnp.where(
        finite,
        jnp.where(grow,
                  jnp.minimum(ls["scale"] * factor, _SCALE_MAX),
                  ls["scale"]),
        jnp.maximum(ls["scale"] / factor, 1.0))
    good = jnp.where(grow, 0, good)
    return {"scale": scale, "good_steps": good}


def build_step_fn(loss_fn, layers, gc, lr_scale):
    """The shared raw (un-jitted) fused train step for both nets:
    forward + loss + backward + gradient normalization + update, with
    loss scaling woven in when the policy asks for it.

    ``loss_fn(params, state, *data_args) -> (loss, new_state)``; the
    returned step has signature
    ``(params, state, opt_state, it, *data_args) ->
    (new_params, new_state, new_opt_state, score)`` — identical to the
    seed step, so jit/scan/shard wrappers need no changes."""
    policy = gc.dtype
    mode = policy.loss_scale_mode()

    if mode is None:
        def step_fn(params, state, opt_state, it, *data_args):
            (score, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, *data_args)
            with opindex.scope("update"):
                new_params, new_opt = apply_layer_updates(
                    layers, gc, params, grads, opt_state, it, lr_scale)
            return new_params, new_state, new_opt, score

        return step_fn

    master = jnp.dtype(policy.param_dtype)

    def step_fn(params, state, opt_state, it, *data_args):
        ls = opt_state[LOSS_SCALE_KEY]
        scale = ls["scale"]

        def scaled_loss(p, s, *a):
            loss, new_state = loss_fn(p, s, *a)
            # aux carries the TRUE loss: the published score must not be
            # a scaled value, and the NaN sentinel keys off it
            with opindex.scope("loss"):
                scaled = loss * scale.astype(loss.dtype)
            return scaled, (loss, new_state)

        (_, (score, new_state)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params, state, *data_args)
        with opindex.scope("update"):
            inv = (1.0 / scale).astype(master)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(master) * inv, grads)
            finite = all_finite(grads)
            new_params, new_opt = apply_layer_updates(
                layers, gc, params, grads, opt_state, it, lr_scale)
            # skip-step: a non-finite gradient selects every param and
            # optimizer slot back to its pre-step value BIT-IDENTICALLY
            # (jnp.where on a scalar predicate is an exact select)
            new_params = jax.tree_util.tree_map(
                lambda n, o: jnp.where(finite, n, o), new_params, params)
            new_opt = jax.tree_util.tree_map(
                lambda n, o: jnp.where(finite, n, o), new_opt, opt_state)
            new_opt[LOSS_SCALE_KEY] = _next_scale_state(ls, finite, mode,
                                                        policy)
        return new_params, new_state, new_opt, score

    return step_fn


def current_loss_scale(net):
    """The net's live loss scale as a float, or None when its policy
    runs unscaled (the observability hook PRECISION.md documents)."""
    opt = getattr(net, "opt_state", None)
    if not isinstance(opt, dict) or LOSS_SCALE_KEY not in opt:
        return None
    return float(opt[LOSS_SCALE_KEY]["scale"])
