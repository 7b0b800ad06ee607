"""Training listeners.

Parity: optimize/api/IterationListener.java + TrainingListener.java and the
impls in optimize/listeners/ (ScoreIterationListener, PerformanceListener,
CollectScoresIterationListener, ComposableIterationListener).

Note: reading ``net.score_value`` forces a device sync; listeners that log
every iteration therefore sample (print frequency) exactly like the
reference, and PerformanceListener measures wall-clock between calls without
forcing a sync unless reporting. ``score_value`` itself stays a lazy device
array — only a listener's own cadence (or an explicit ``float()``) pulls it
to the host.

``needs_per_iteration`` (class attribute, default True): declares whether
the listener's semantics depend on being invoked at the real wall-clock
moment each iteration finishes (timing listeners, per-step param pulls).
Listeners that only consume ``(iteration, score_value)`` pairs declare
False; when every attached listener does, ``fit`` may dispatch several
steps as one jitted scan chunk and REPLAY ``iteration_done`` per inner
iteration afterwards with identical (iteration, score) values.
"""

from __future__ import annotations

import logging
import time

logger = logging.getLogger("deeplearning4j_tpu")


class TrainingListener:
    """Base listener (TrainingListener.java parity: onEpochStart/End,
    iterationDone; forward/backward hooks are meaningless inside one fused
    XLA step, so they are not exposed)."""

    # True = must run at the real per-step boundary (timings, param pulls);
    # False = only consumes (iteration, score) and tolerates chunked
    # dispatch with post-hoc replay (see module docstring).
    needs_per_iteration = True

    def iteration_done(self, net, iteration: int, epoch: int):
        pass

    def on_epoch_start(self, net):
        pass

    def on_epoch_end(self, net):
        pass

    def on_recovery(self, net, event):
        """Resilience hook: called by the TrainingSupervisor with a
        resilience.RecoveryEvent for every checkpoint / resume / retry /
        rollback / preemption (no reference analogue — the reference has
        no recovery loop to observe)."""
        pass


class ScoreIterationListener(TrainingListener):
    """Logs the loss every N iterations (ScoreIterationListener parity)."""

    needs_per_iteration = False  # cadence-sampled score only

    def __init__(self, print_iterations: int = 10, out=None):
        self.print_iterations = max(1, print_iterations)
        self.out = out

    def iteration_done(self, net, iteration, epoch):
        if iteration % self.print_iterations == 0:
            msg = (f"Score at iteration {iteration} is "
                   f"{float(net.score_value):.6f}")
            if self.out is not None:
                print(msg, file=self.out)
            else:
                logger.info(msg)


class CollectScoresIterationListener(TrainingListener):
    """Collects (iteration, score) pairs (CollectScoresIterationListener
    parity)."""

    needs_per_iteration = False  # cadence-sampled score only

    def __init__(self, frequency: int = 1):
        self.frequency = max(1, frequency)
        self.scores: list[tuple[int, float]] = []

    def iteration_done(self, net, iteration, epoch):
        if iteration % self.frequency == 0:
            self.scores.append((iteration, float(net.score_value)))


class PerformanceListener(TrainingListener):
    """Throughput reporting (PerformanceListener parity: iterations/sec,
    examples/sec, iteration wall time) + MFU when ``report_mfu`` is set:
    per-step FLOPs come from XLA's cost model on the compiled train step
    (SURVEY.md §5.1 — the reference has no MFU concept; the TPU framework
    reports it first-class), peak from the device kind."""

    needs_per_iteration = True  # measures real wall-clock per step

    def __init__(self, frequency: int = 10, report_examples: bool = True,
                 flops_per_step: float | None = None,
                 report_mfu: bool = False):
        self.frequency = max(1, frequency)
        self.report_examples = report_examples
        self.flops_per_step = flops_per_step  # net.step_cost_analysis(ds)["flops"]
        # report_mfu without explicit flops: use the FLOPs the fit loop
        # read from the dispatched step's cost model (net.flops_per_step)
        self.report_mfu = bool(report_mfu) or flops_per_step is not None
        self.records: list[dict] = []
        self._last_time = None
        self._last_iter = None
        self._examples = 0

    def _resolve_flops(self, net) -> float | None:
        if self.flops_per_step:
            return self.flops_per_step
        if self.report_mfu:
            return getattr(net, "flops_per_step", None)
        return None

    def _peak(self):
        import jax

        from deeplearning4j_tpu.utils.perf import peak_flops
        return peak_flops(jax.devices()[0])

    def iteration_done(self, net, iteration, epoch):
        now = time.perf_counter()
        if self._last_time is None:
            self._last_time, self._last_iter = now, iteration
            self._examples = 0
            return
        self._examples += getattr(net, "last_batch_examples", 0)
        if iteration % self.frequency == 0:
            dt = now - self._last_time
            iters = iteration - self._last_iter
            rec = {
                "iteration": iteration,
                "iterations_per_sec": iters / dt if dt > 0 else float("inf"),
                "ms_per_iteration": 1000.0 * dt / max(iters, 1),
            }
            msg = (f"iteration {iteration}: "
                   f"{rec['iterations_per_sec']:.1f} it/s, "
                   f"{rec['ms_per_iteration']:.2f} ms/it")
            if self.report_examples and self._examples:
                rec["examples_per_sec"] = (
                    self._examples / dt if dt > 0 else float("inf"))
                msg += f", {rec['examples_per_sec']:.1f} examples/s"
            flops = self._resolve_flops(net)
            if flops and dt > 0:
                peak = self._peak()
                if peak:
                    mfu = flops * iters / dt / peak
                    if 0.0 < mfu <= 1.0:  # never publish impossible MFU
                        rec["mfu"] = mfu
                        msg += f", MFU {100 * mfu:.1f}%"
            self.records.append(rec)
            logger.info(msg)
            self._last_time, self._last_iter = now, iteration
            self._examples = 0


class RecoveryEventListener(TrainingListener):
    """Collects (and optionally logs) supervisor recovery events — the
    listener-tier view of the resilience runtime's restarts, rollbacks
    and retries (ResilienceStats carries the counter view)."""

    needs_per_iteration = False  # only observes recovery events

    def __init__(self, log: bool = True):
        self.log = log
        self.events: list = []

    def on_recovery(self, net, event):
        self.events.append(event)
        if self.log:
            logger.warning("recovery: %s", event)

    def counts(self) -> dict:
        out: dict[str, int] = {}
        for e in self.events:
            out[e.kind] = out.get(e.kind, 0) + 1
        return out


class ComposableIterationListener(TrainingListener):
    def __init__(self, *listeners):
        self.listeners = listeners

    @property
    def needs_per_iteration(self):
        return any(getattr(l, "needs_per_iteration", True)
                   for l in self.listeners)

    def iteration_done(self, net, iteration, epoch):
        for l in self.listeners:
            l.iteration_done(net, iteration, epoch)

    def on_epoch_start(self, net):
        for l in self.listeners:
            l.on_epoch_start(net)

    def on_epoch_end(self, net):
        for l in self.listeners:
            l.on_epoch_end(net)

    def on_recovery(self, net, event):
        for l in self.listeners:
            l.on_recovery(net, event)


class ProfilerListener(TrainingListener):
    """Captures a JAX/XLA profiler trace for a window of training
    iterations (SURVEY.md §5.1: the reference has only wall-clock
    listeners; the TPU framework exposes the real profiler). The trace
    (xplane.pb) lands in ``log_dir`` and opens with xprof/tensorboard;
    PERF.md documents the in-repo parsing recipe."""

    def __init__(self, log_dir: str, start_iteration: int = 5,
                 num_iterations: int = 5):
        self.log_dir = log_dir
        self.start_iteration = start_iteration
        self.num_iterations = max(1, num_iterations)
        self._active = False
        self.captured = False
        import atexit
        # the JAX trace is process-wide: if training ends mid-window
        # (short fit_batch loop, exception inside fit), the trace must
        # still be flushed or it is silently lost AND blocks any later
        # start_trace in this process
        atexit.register(self.close)

    def _warn_once(self, what: str, exc: Exception):
        if not getattr(self, "_warned", False):
            self._warned = True
            import logging
            logging.getLogger("deeplearning4j_tpu").warning(
                "ProfilerListener: %s failed (%s: %s) — profiling "
                "disabled for this window, training continues",
                what, type(exc).__name__, exc)

    def _stop(self, net):
        import jax
        # sync so the trace includes the in-flight device work
        if net is not None and net.score_value is not None:
            try:
                float(net.score_value)
            except Exception:
                pass
        # idempotent: a second listener instance (or anything else) may
        # already have stopped the process-wide trace — stop_trace then
        # raises, which must not abort training or leave _active stuck
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            self._warn_once("stop_trace", e)
        self._active = False
        self.captured = True

    def close(self, net=None):
        """Flush the trace if still recording (safe to call anytime)."""
        if self._active:
            self._stop(net)

    def iteration_done(self, net, iteration, epoch):
        import jax
        if (not self.captured and not self._active
                and iteration >= self.start_iteration):
            # idempotent: the process-wide trace may already be running
            # (a re-attached listener, or an outer profiling harness) —
            # start_trace raises; warn once, mark captured, keep training
            try:
                jax.profiler.start_trace(self.log_dir)
            except Exception as e:
                self._warn_once("start_trace", e)
                self.captured = True
                return
            self._active = True
            self._stop_at = iteration + self.num_iterations
            return
        if self._active and iteration >= self._stop_at:
            self._stop(net)

    def on_epoch_end(self, net):
        self.close(net)  # epoch shorter than the window: flush cleanly


class ParamAndGradientIterationListener(TrainingListener):
    """Per-iteration param/update magnitude logging to delimited text.

    Parity: optimize/listeners/ParamAndGradientIterationListener.java —
    one row per sampled iteration: ``n``, ``score``, then per parameter
    tensor mean / min / max / meanAbsValue for the PARAMETER and for the
    step's weight change. The reference logs raw gradients; here
    forward+backward+updater fuse into one XLA program (the gradient is
    never materialized on the host), so the logged "G" columns are the
    applied per-step update delta — the same tuning/debugging signal the
    reference's columns serve (an update IS the updater-scaled gradient),
    at zero extra device traffic. Column names keep the reference's
    ``_meanG``/``_minG``/``_maxG``/``_meanAbsValueG`` suffixes so
    downstream tooling parses both.
    """

    def __init__(self, iterations: int = 1, *, print_header: bool = True,
                 print_mean: bool = True, print_min_max: bool = True,
                 print_mean_abs: bool = True, file=None,
                 output_to_console: bool = False, delimiter: str = "\t"):
        self.iterations = max(1, iterations)
        self.print_header = print_header
        self.print_mean = print_mean
        self.print_min_max = print_min_max
        self.print_mean_abs = print_mean_abs
        self.file = file
        self.output_to_console = output_to_console
        self.delimiter = delimiter
        self._count = 0
        self._prev = None
        self._wrote_header = False

    # -- helpers ----------------------------------------------------------
    def _flat_params(self, net):
        import jax
        import numpy as np
        out = {}
        for ln, sub in net.params.items():
            for pn, arr in sub.items():
                out[f"{ln}_{pn}"] = np.asarray(jax.device_get(arr),
                                               dtype=np.float64)
        return out

    def _stat_cols(self, arr):
        import numpy as np
        cols = []
        if self.print_mean:
            cols.append(float(np.mean(arr)) if arr.size else 0.0)
        if self.print_min_max:
            cols.append(float(np.min(arr)) if arr.size else 0.0)
            cols.append(float(np.max(arr)) if arr.size else 0.0)
        if self.print_mean_abs:
            cols.append(float(np.mean(np.abs(arr))) if arr.size else 0.0)
        return cols

    def _emit(self, line: str):
        if self.file is not None:
            self.file.write(line + "\n")
            self.file.flush()
        if self.output_to_console:
            print(line)
        if self.file is None and not self.output_to_console:
            logger.info(line)

    # -- listener ---------------------------------------------------------
    def on_epoch_start(self, net):
        # snapshot pre-step params so the FIRST sampled row has real
        # update columns (without this the first delta would be zero)
        if self._prev is None and net.params is not None:
            self._prev = self._flat_params(net)

    def iteration_done(self, net, iteration, epoch):
        import numpy as np
        self._count += 1
        # fetch device params only for sampled rows and the iteration just
        # before one (the delta's left edge) — a every-step device->host
        # pull of the full param tree would stall the dispatch pipeline
        # the fused step exists to keep full
        nxt = self._count + 1
        if not (self._count % self.iterations == 0
                or nxt % self.iterations == 0):
            return
        params = self._flat_params(net)
        if self.print_header and not self._wrote_header:
            names = []
            for s in params:
                if self.print_mean:
                    names.append(f"{s}_mean")
                if self.print_min_max:
                    names += [f"{s}_min", f"{s}_max"]
                if self.print_mean_abs:
                    names.append(f"{s}_meanAbsValue")
                if self.print_mean:
                    names.append(f"{s}_meanG")
                if self.print_min_max:
                    names += [f"{s}_minG", f"{s}_maxG"]
                if self.print_mean_abs:
                    names.append(f"{s}_meanAbsValueG")
            self._emit(self.delimiter.join(["n", "score"] + names))
            self._wrote_header = True
        if self._count % self.iterations != 0:
            self._prev = params
            return
        cols = [str(self._count), repr(float(net.score_value))]
        prev = self._prev if self._prev is not None else params
        for s, arr in params.items():
            delta = arr - prev.get(s, arr)
            for v in self._stat_cols(arr) + self._stat_cols(delta):
                cols.append(repr(v))
        self._emit(self.delimiter.join(cols))
        self._prev = params
