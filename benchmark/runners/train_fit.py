"""Runner for traffic of kind ``train_fit``: one training job through
``net.fit(iterator)`` with default arguments.

The job a user runs is ``net.fit(their_iterator)`` with a listener that
looks at the score now and then. So the runner feeds ``fit`` a host-side
ring of seeded batches and attaches one listener. The listener is the
window's clock: at every dispatch boundary (one batch, or one chunk of
batches where ``fit`` scans several steps in one program) it waits for
the score of the *previous* dispatch and stamps the time. That barrier
lags one dispatch, so the device always has the next program queued and
the host still cannot run further ahead than one dispatch, which bounds
what the run keeps staged on the device.

``train_examples_per_s`` is the examples of one dispatch over the median
time between two barriers of the window, so where ``--seconds`` cuts a
chunk does not move it, and neither does a stall of the host: on the
shared one-chip machines one run in twenty loses 0.1 to 1.5 s to one (v5e,
PR 22), which moved the rate over the whole window by 1 to 15% and moves
a few intervals of tens without moving their median. What the median
leaves out is reported beside it (``fit_stall_share``: the rate over the
whole window against the rate at the median dispatch). The ring stops on
the first dispatch boundary after ``--seconds``.

Traffic parameters (``benchmark/traffic/<mix>.json``): ``batch`` (global),
``seq_len`` (sequence configurations), ``mesh`` (axis sizes, optional),
``ring_batches``, ``warmup_steps``, ``trace_seconds``, and ``rehearsal``
(overrides for ``--rehearse``).

``run.py`` imports this module after it has set the environment and
imported jax, so jax and the program are imported at the top.
"""

from __future__ import annotations

import glob
import os
import shutil
import threading
import time
from importlib import import_module

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from benchmark import xplane
from benchmark.manifest import resolve
from benchmark.measure import Measurement
from benchmark.reference import mcxent_mean
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import DataSetIterator
from deeplearning4j_tpu.observability import metrics as obs
from deeplearning4j_tpu.observability.trace import Tracer, set_tracer
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from deeplearning4j_tpu.parallel.mesh import make_mesh

# --rehearse only: the CPU backend resolves fit's "auto" arguments to the
# per-batch path, so the rehearsal asks for the chip's defaults by name.
REHEARSAL_FIT_KWARGS = {"multi_step": 8, "device_prefetch": True}


# ------------------------------------------------------------------ inputs
def _onehot(ids, width):
    return np.eye(width, dtype=np.float32)[ids]


def make_ring(spec: dict, traffic: dict, seed: int) -> list:
    """``ring_batches`` distinct seeded batches as ``(features, labels)``
    float32 arrays, in the layout ``DataSet`` feeds today."""
    rng = np.random.default_rng(seed)
    b = traffic["batch"]
    ring = []
    for _ in range(traffic["ring_batches"]):
        if spec["kind"] == "image":
            # uint8 noise standardised to zero mean, unit variance: ten
            # times cheaper to draw than float normals at 154 MB a batch
            h, w, c = spec["shape"]
            x = rng.integers(0, 256, (b, h, w, c), dtype=np.uint8)
            x = (x.astype(np.float32) - 127.5) / 73.9
            y = _onehot(rng.integers(0, spec["classes"], b), spec["classes"])
        elif spec["kind"] == "chars":
            # next-character targets: labels are the inputs shifted by one
            ids = rng.integers(0, spec["vocab"], (b, traffic["seq_len"] + 1))
            x = _onehot(ids[:, :-1], spec["vocab"])
            y = _onehot(ids[:, 1:], spec["vocab"])
        else:
            raise ValueError(f"unknown input kind {spec['kind']!r}")
        ring.append((x, y))
    return ring


def make_sample(spec: dict, seed: int):
    """A small seeded batch of its own for the forward check."""
    rng = np.random.default_rng(seed + 1_000_003)
    n = spec["check_rows"]
    if spec["kind"] == "image":
        h, w, c = spec["shape"]
        return rng.normal(0, 1, (n, h, w, c)).astype(np.float32)
    ids = rng.integers(0, spec["vocab"], (n, spec["check_seq_len"]))
    return _onehot(ids, spec["vocab"])


# ------------------------------------------------------- iterator, listener
class RingIterator(DataSetIterator):
    """Cycles ``ring`` and asks ``go_on()`` before every group of ``group``
    batches, so that ``fit`` never sees a partial chunk (a new chunk
    length would compile inside the window)."""

    def __init__(self, ring, group: int, go_on):
        self.batches = [DataSet(x, y) for x, y in ring]
        self.group = group
        self.go_on = go_on

    def __iter__(self):
        i = 0
        while self.go_on():
            for _ in range(self.group):
                yield self.batches[i % len(self.batches)]
                i += 1


class WindowListener(TrainingListener):
    """Stamps the completion of each dispatch, one dispatch late, and
    starts the profiler (``start_trace``) at the first barrier after
    ``trace_at``."""

    needs_per_iteration = False     # fit may keep its chunked path

    def __init__(self, trace_at=None, start_trace=None):
        self.trace_at = trace_at
        self.start_trace = start_trace
        self.scores = []            # lazy device scalars, one per step
        self.barriers = []          # (perf_counter, steps completed)
        self.traced_from = None     # index into barriers
        self._pending = None        # (score, steps) of the last dispatch

    def iteration_done(self, net, iteration, epoch):
        self.scores.append(net.score_value)
        if iteration != net.iteration:
            return                  # replay inside a chunk
        previous, self._pending = (self._pending,
                                   (net.score_value, len(self.scores)))
        if previous is not None:
            self._barrier(*previous)

    def _barrier(self, score, steps):
        score.block_until_ready()
        now = time.perf_counter()
        self.barriers.append((now, steps))
        if (self.start_trace is not None and self.traced_from is None
                and now >= self.trace_at):
            self.start_trace()
            # the profiler's start stalls the host: the next interval
            # belongs to neither part of the window
            self.traced_from = len(self.barriers)

    def finish(self):
        if self._pending is not None:
            self._barrier(*self._pending)
            self._pending = None


def _rate(barriers, batch):
    """Examples per second between the first and last of ``barriers``."""
    if len(barriers) < 2:
        return None
    (t0, s0), (t1, s1) = barriers[0], barriers[-1]
    return (s1 - s0) * batch / (t1 - t0)


def _seconds_per_step(barriers):
    """Per dispatch, the time since the barrier before it over its steps."""
    times, steps = np.asarray(barriers, np.float64).T
    return np.diff(times) / np.diff(steps)


def _median_rate(barriers, batch):
    """Examples per second at the median dispatch of ``barriers``."""
    if len(barriers) < 2:
        return None
    return batch / float(np.median(_seconds_per_step(barriers)))


# ------------------------------------------------------------------ checks
def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-12)


def _forward_error(probabilities, ref_logits):
    """``max |log p_system - log_softmax(reference)|`` over the entries
    both can represent, relative to the spread of the reference logits.
    Softmax outputs below float32's range are left out, and a check that
    left out most entries counts as failed by the caller."""
    ref = jnp.asarray(ref_logits, jnp.float32)
    ref_logp = jax.nn.log_softmax(ref, axis=-1)
    p = jnp.asarray(probabilities, jnp.float32).reshape(ref.shape)
    usable = (p > 1e-37) & (ref_logp > -80.0)
    diff = jnp.where(usable, jnp.abs(jnp.log(jnp.maximum(p, 1e-37))
                                     - ref_logp), 0.0)
    spread = jnp.abs(ref - jnp.mean(ref, axis=-1, keepdims=True)).max()
    return (float(diff.max() / spread), float(jnp.mean(usable)),
            bool(jnp.all(jnp.isfinite(p))))


class MemorySampler(threading.Thread):
    """Peak memory of the fullest chip, sampled every 50 ms from the
    first warm-up step to the end of the window (the reference's own
    memory comes later and is not counted).

    The TPU runtime keeps two books: arrays (``bytes_in_use``:
    parameters, staged batches) and the scratch it reserves for the
    program that runs (``bytes_reserved``: activations and every other
    temporary of the step). A training step's memory is nearly all of the
    second kind, and the two peak at different moments, so neither
    counter's own peak is the chip's, and their sum can pass the chip's
    size. The peak reported is the largest sum seen in one sample.
    PERF.md, Findings PR 22, has how the two were told apart."""

    PERIOD_S = 0.05

    def __init__(self, devices):
        super().__init__(name="bench-memory-sampler", daemon=True)
        self.devices = list(devices)
        self.peak = {"peak_bytes": 0, "arrays": 0, "programs": 0, "limit": 0}
        self._stop_event = threading.Event()

    def sample(self):
        for d in self.devices:
            stats = d.memory_stats() or {}
            arrays = stats.get("bytes_in_use", 0)
            programs = stats.get("bytes_reserved", 0)
            if arrays + programs > self.peak["peak_bytes"]:
                self.peak = {"peak_bytes": arrays + programs,
                             "arrays": arrays, "programs": programs,
                             "limit": stats.get("bytes_limit", 0)}

    def run(self):
        while not self._stop_event.wait(self.PERIOD_S):
            self.sample()

    def finish(self) -> dict:
        self._stop_event.set()
        self.join()
        self.sample()
        return self.peak


# --------------------------------------------------------------------- run
def _build(ctx):
    """The net from the zoo's own init and the seed, on its mesh if the
    traffic names one; returns it with the sharding of a batch."""
    config = ctx.cell.config
    net = resolve(config["factory"])(seed=ctx.seed, **config["kwargs"])
    mesh_axes = ctx.cell.traffic.get("mesh")
    if not mesh_axes:
        return net, None
    n = int(np.prod(list(mesh_axes.values())))
    mesh = make_mesh(mesh_axes, devices=jax.devices()[:n])
    net.use_mesh(mesh)
    return net, NamedSharding(mesh, PartitionSpec("data"))


def _warm_up(net, ring, steps: int, fit_kwargs: dict):
    """``steps`` batches through the same path: compiles every program
    the window uses. Returns the losses and how many steps ``fit`` put
    into one dispatch."""
    tracer = Tracer()
    previous = set_tracer(tracer)
    listener = WindowListener()
    net.set_listeners(listener)
    left = [steps]

    def go_on():
        left[0] -= 1
        return left[0] >= 0

    try:
        net.fit(RingIterator(ring, 1, go_on), **fit_kwargs)
        listener.finish()
    finally:
        set_tracer(previous)
    group = max((s.attrs or {}).get("steps", 1)
                for s in tracer.spans() if s.name == "host_dispatch")
    return [float(s) for s in jax.device_get(listener.scores)], group


def _start_profiler(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    # device planes only. The host tracer records one event per tile of
    # every host-to-device layout change (millions a second at 154 MB a
    # batch), at level 1 as at level 2, which slowed the input path until
    # ResNet-50's device sat idle 60% of the traced part (PERF.md,
    # Findings PR 22). Host spans come from the program's tracer instead,
    # placed by the unix clock.
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def _window(ctx, net, ring, group: int, fit_kwargs: dict, trace_dir: str):
    """The measured window. Returns the listener (barriers, scores), the
    window's tracer with the perf_counter reading at its epoch, and
    whether the profiler ran."""
    tracer = Tracer()
    previous = set_tracer(tracer)
    tracer_epoch = time.perf_counter()
    profiled = []

    def start_trace():
        _start_profiler(trace_dir)
        profiled.append(True)

    deadline = time.perf_counter() + ctx.seconds
    listener = WindowListener(
        trace_at=deadline - ctx.cell.traffic["trace_seconds"],
        start_trace=start_trace if ctx.trace else None)
    net.set_listeners(listener)
    try:
        net.fit(RingIterator(ring, group,
                               lambda: time.perf_counter() < deadline),
                **fit_kwargs)
        listener.finish()               # the window ends in a host read
    finally:
        if profiled:
            jax.profiler.stop_trace()
        set_tracer(previous)
    return listener, tracer, tracer_epoch, bool(profiled)


def _reduce_profile(trace_dir: str, tracer):
    """The device trace with the window's spans laid on its clock."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    profile = xplane.load(files[0])
    epoch_unix = tracer.epoch_unix()
    for sp in tracer.spans():
        profile.place(sp.thread, sp.name, epoch_unix + sp.ts_us * 1e-6,
                      sp.dur_us * 1e-6)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return xplane.reduce(profile)


def _check(ctx, sharding, init, ring, sample, init_probabilities,
           first_loss: float) -> dict:
    """The system against the configuration's plain float32 reference, on
    the seeded initial parameters ``init``. Training path: the first
    step's loss on the same batch (batch statistics, mean over the
    batch). Inference path: the forward on a seeded sample, entry by
    entry (``init_probabilities`` is what ``net.output`` gave for it
    before the first step). Neither depends on how long the window was or
    how far it memorised the ring. The reference runs after the window,
    so that its memory does not count into the cell's peak."""
    reference = import_module(ctx.cell.config["reference"])
    place = ((lambda a: jax.device_put(a, sharding)) if sharding is not None
             else jnp.asarray)
    x0, y0 = ring[0]
    with jax.default_matmul_precision("highest"):
        ref_loss = float(jax.jit(
            lambda p, s, x, y: mcxent_mean(reference.logits(p, s, x, True), y)
        )(*init, place(x0), place(y0)))
        ref_logits = jax.jit(lambda p, s, x: reference.logits(p, s, x, False))(
            *init, sample)
    err, compared, finite = _forward_error(init_probabilities, ref_logits)
    return {
        "first_loss": {"system": first_loss, "reference": ref_loss,
                       "rel_err": _rel(first_loss, ref_loss),
                       "tol": reference.LOSS_RTOL},
        "forward_logits": {"rel_err": err, "compared_share": compared,
                           "finite": finite, "tol": reference.LOGITS_RTOL},
        "ok": bool(_rel(first_loss, ref_loss) <= reference.LOSS_RTOL
                   and finite and compared >= 0.5
                   and err <= reference.LOGITS_RTOL),
    }


def run(ctx) -> dict:
    config, traffic = ctx.cell.config, ctx.cell.traffic
    fit_kwargs = dict(REHEARSAL_FIT_KWARGS) if ctx.rehearse else {}
    batch = traffic["batch"]
    snap_setup = obs.compile_snapshot()
    phases = {"program_import": time.time() - ctx.t0}
    net, sharding = _build(ctx)
    # what the checks compare, taken before the first step moves the
    # parameters
    init = jax.device_get((net.params, net.state))
    sample = make_sample(config["input"], ctx.seed)
    init_probabilities = np.asarray(net.output(sample))
    phases["build"] = time.time() - ctx.t0 - sum(phases.values())
    ring = make_ring(config["input"], traffic, ctx.seed)
    phases["ring"] = time.time() - ctx.t0 - sum(phases.values())
    trace_dir = os.path.join(ctx.root, ".bench_trace", ctx.cell.name)

    sampler = MemorySampler(jax.local_devices())
    sampler.start()
    try:
        warm_losses, group = _warm_up(net, ring, traffic["warmup_steps"],
                                      fit_kwargs)
        setup_compile = obs.compile_delta(snap_setup)
        snap_window = obs.compile_snapshot()
        setup_s = time.time() - ctx.t0
        phases["warm_up"] = setup_s - sum(phases.values())
        listener, tracer, tracer_epoch, profiled = _window(
            ctx, net, ring, group, fit_kwargs, trace_dir)
        window_compile = obs.compile_delta(snap_window)
    finally:
        memory = sampler.finish()

    barriers = listener.barriers
    if len(barriers) < 2:
        raise RuntimeError(f"the window held {len(barriers)} dispatches; "
                           "a rate needs two")
    window_s = barriers[-1][0] - barriers[0][0]
    lo_us = (barriers[0][0] - tracer_epoch) * 1e6
    hi_us = (barriers[-1][0] - tracer_epoch) * 1e6
    spans = [s for s in tracer.spans()
             if s.ts_us >= lo_us and s.ts_us + s.dur_us <= hi_us]
    losses = np.asarray(jax.device_get(listener.scores), np.float64)

    counters = {
        "window_steps": barriers[-1][1] - barriers[0][1],
        "steps_per_dispatch": group,
        "setup_cache_misses": setup_compile["cache_misses"],
        "setup_cache_hits": setup_compile["cache_hits"],
        "setup_compile_s": setup_compile["seconds"],
        "window_compiles": window_compile["count"],
        "memory_peak_bytes": memory["peak_bytes"],
        "memory_arrays_bytes_at_peak": memory["arrays"],
        "memory_programs_bytes_at_peak": memory["programs"],
        "memory_limit_bytes": memory["limit"],
    }
    split = listener.traced_from
    if split is not None:
        counters["rate_profiler_off"] = _rate(barriers[:split], batch)
        counters["rate_profiler_on"] = _rate(barriers[split:], batch)
    # the profiler's start stalls the host, which is no stall of the fit
    undisturbed = barriers[:split]
    counters["rate_whole_window"] = _rate(undisturbed, batch)
    counters["rate_median_dispatch"] = _median_rate(undisturbed, batch)
    reduction = _reduce_profile(trace_dir, tracer) if profiled else None

    checks = _check(ctx, sharding, init, ring, sample, init_probabilities,
                    warm_losses[0])
    # lr 0.1 without warm-up can raise the loss over the first steps; by
    # the end of a window on a ring of two batches it has to be under
    # where it began
    checks["loss"] = {"first": warm_losses[0], "warmup_last": warm_losses[-1],
                      "window_last": float(losses[-1])}
    failed = int(np.sum(~np.isfinite(losses)))
    correct = bool(checks.pop("ok") and not failed
                   and losses[-1] < warm_losses[0]
                   and window_compile["count"] == 0)

    return {
        "correct": correct,
        "attempted": int(len(losses)),
        "failed": failed,
        "end_to_end": {"train_examples_per_s": _median_rate(barriers, batch),
                       "setup_s": setup_s},
        "measurement": Measurement(
            config=config, traffic=traffic, chips=ctx.cell.chips,
            peaks=ctx.peaks, window_s=window_s, spans=spans,
            counters=counters, trace=reduction),
        "memory_peak_bytes": int(memory["peak_bytes"]),
        "info": {"checks": checks, "counters": counters,
                 "window_s": window_s, "dispatches": len(barriers),
                 "step_s_quantiles": dict(zip(
                     ("min", "p10", "median", "p90", "max"),
                     np.quantile(_seconds_per_step(barriers),
                                 (0, 0.1, 0.5, 0.9, 1)).tolist())),
                 "train_examples_per_s": _median_rate(barriers, batch),
                 "setup_s": setup_s, "setup_phases_s": phases},
    }
