"""Fault-tolerant training runtime: a supervisor around ``fit``.

SURVEY.md §5.3 calls preemption-resume the TPU stack's fault-tolerance
answer, and utils/checkpoint.py provides the raw primitive — but nothing
in the seed *supervised* a long fit() run: a crash, a NaN blow-up or a
TPU preemption simply lost the run. The TrainingSupervisor closes that
gap (the TensorFlow checkpoint/recovery loop of Abadi et al. §4.4,
rendered onto this framework's fused-step training):

- **Periodic checkpointing** to fresh ``step_<n>`` directories (the
  crash-atomic discipline utils/checkpoint.py documents), plus an
  atomically-renamed ``LATEST`` pointer file and retention GC that keeps
  the newest ``keep_checkpoints`` valid steps.
- **Auto-resume**: a relaunched supervisor discovers the newest *valid*
  checkpoint (``find_latest_checkpoint`` skips partial saves missing
  ``meta.json``) and continues to the same absolute target step.
- **Transient-step retry**: exceptions of the configured types are
  retried with exponential backoff before giving up.
- **NaN/Inf sentinel**: a non-finite loss rolls the net back to the last
  good checkpoint and backs off the learning rate
  (``net.set_lr_scale``); poisoned parameters are never checkpointed.
- **Preemption (SIGTERM)**: the in-flight step finishes, a final
  checkpoint is written, and ``run`` returns with status ``preempted``.
- **Cross-process coordination**: under ``jax.process_count() > 1``
  every recovery decision above is routed through the consensus layer
  in parallel/distributed.py (``agree_decision`` over tiny recovery
  codes with a ``DL4J_TPU_COLLECTIVE_TIMEOUT_S`` deadline): any-NaN →
  every process rolls back in lockstep, any-transient → every process
  retries on the same backoff schedule, SIGTERM anywhere → fleet-wide
  preemption with one final barriered checkpoint. A consensus round
  that times out names a dead peer: the supervisor flushes a
  ``peer_lost`` flight record, writes NO partial checkpoint, and
  returns status ``peer_lost`` so a launcher (resilience/launcher.py)
  can relaunch — possibly SHRUNK, whereupon the elastic reshard
  restore re-lays the run onto the smaller fleet.

Every recovery action is emitted as a :class:`RecoveryEvent` through the
net's listeners (``TrainingListener.on_recovery``), counted in
:class:`ResilienceStats` (a ``/metrics``-style ``snapshot()``), and the
checkpoint saves are timed as ``checkpoint_barrier`` phases when a
``parallel.stats.TrainingStatsCollector`` is supplied.

Deterministic fault injection for all of these paths lives in
resilience/faultinject.py; scripts/chaos_train.py drives them end to end
and asserts bit-identical final parameters vs an uninterrupted run.
"""

from __future__ import annotations

import logging
import math
import os
import shutil
import signal
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from deeplearning4j_tpu.observability import goodput as _goodput
from deeplearning4j_tpu.observability import metrics as _obs_metrics
from deeplearning4j_tpu.observability.trace import get_tracer as _get_tracer

logger = logging.getLogger("deeplearning4j_tpu")

_LATEST_POINTER = "LATEST"


class TrainingDivergedError(RuntimeError):
    """The NaN sentinel exhausted ``max_nan_rollbacks`` — training keeps
    producing non-finite losses even after rollback + LR backoff."""


@dataclass(frozen=True)
class RecoveryEvent:
    """One supervisor action: kind is ``resume`` | ``checkpoint`` |
    ``retry`` | ``rollback`` | ``preempt`` | ``gc`` | ``reshard`` |
    ``peer_lost``."""
    kind: str
    step: int
    detail: str = ""

    def __str__(self):
        return f"[{self.kind} @ step {self.step}] {self.detail}"


class ResilienceStats:
    """Thread-safe recovery counters — the observability surface the
    serving tier's ServingStats provides for inference, for training:
    restarts, rollbacks and retry counts are numbers a dashboard can
    poll, not log lines."""

    def __init__(self):
        self._lock = threading.Lock()
        self.resumes = 0
        self.checkpoints = 0
        self.retries = 0
        self.rollbacks = 0
        self.preemptions = 0
        self.gc_removed = 0
        self.nan_check_lag = 0
        self.reshards = 0
        self.peer_losses = 0

    def bump(self, counter: str, n: int = 1):
        with self._lock:
            setattr(self, counter, getattr(self, counter) + n)

    def note_nan_check_lag(self, lag: int):
        """Record how many steps behind the lazy NaN sentinel was when it
        materialized a score (max over the run; 0 = checked at the step
        boundary like the eager PR2 sentinel)."""
        with self._lock:
            self.nan_check_lag = max(self.nan_check_lag, int(lag))

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "resumes_total": self.resumes,
                "checkpoints_total": self.checkpoints,
                "retries_total": self.retries,
                "rollbacks_total": self.rollbacks,
                "preemptions_total": self.preemptions,
                "checkpoints_gc_total": self.gc_removed,
                "nan_check_lag_max": self.nan_check_lag,
                "reshards_total": self.reshards,
                "peer_losses_total": self.peer_losses,
            }

    # ------------------------------------------- unified-registry bridge
    # Mirrors ServingStats.attach_to_registry: the counters stay the
    # source of truth, the registry renders them at scrape time.

    _HELP = {
        "resumes_total": "Runs resumed from a checkpoint",
        "checkpoints_total": "Checkpoints committed",
        "retries_total": "Transient step failures retried",
        "rollbacks_total": "NaN/Inf rollbacks to the last good checkpoint",
        "preemptions_total": "Clean preemption exits",
        "checkpoints_gc_total": "Old/partial checkpoints removed by GC",
        "nan_check_lag_max": "Max steps the lazy NaN sentinel lagged",
        "reshards_total": "Resumes that re-laid the run onto a "
                          "different fleet size",
        "peer_losses_total": "Consensus timeouts naming a dead peer "
                             "(the run exited with status peer_lost)",
    }

    def metric_families(self, labels=None):
        from deeplearning4j_tpu.observability.metrics import MetricFamily

        L = dict(labels or {})
        out = []
        for key, value in self.snapshot().items():
            kind = "gauge" if key == "nan_check_lag_max" else "counter"
            out.append(MetricFamily(f"dl4j_resilience_{key}", kind,
                                    self._HELP[key]).add(value, L))
        return out

    def attach_to_registry(self, registry=None, *, labels=None):
        from deeplearning4j_tpu.observability.metrics import get_registry

        self.detach_from_registry()
        reg = registry if registry is not None else get_registry()

        def _collect():
            return self.metric_families(labels)

        reg.register_collector(_collect)
        self._registry, self._collector = reg, _collect
        return reg

    def detach_from_registry(self):
        reg = getattr(self, "_registry", None)
        if reg is not None:
            reg.unregister_collector(self._collector)
            self._registry = self._collector = None


def _default_retry_on():
    from deeplearning4j_tpu.resilience.faultinject import TransientStepError
    return (TransientStepError,)


@dataclass
class SupervisorConfig:
    """Knobs for one supervised run (RESILIENCE.md has the failure
    matrix these map onto)."""

    checkpoint_dir: str
    checkpoint_every_steps: int = 100
    keep_checkpoints: int = 3
    resume: bool = True
    #: exception types treated as transient and retried with backoff;
    #: anything else propagates immediately
    retry_on: tuple = field(default_factory=_default_retry_on)
    max_step_retries: int = 3
    backoff_initial_s: float = 0.05
    backoff_factor: float = 2.0
    backoff_max_s: float = 5.0
    #: multiply the learning rate by this after each NaN rollback
    nan_lr_backoff: float = 0.5
    max_nan_rollbacks: int = 3
    #: check the loss for NaN/Inf every n steps. Scores are kept as lazy
    #: device arrays and only materialized (device sync) at the check
    #: boundary, before every checkpoint snapshot (so poison is still
    #: never checkpointed — the rollback window is unchanged), and at
    #: exit; 1 = the eager per-step sentinel, larger values trade
    #: detection lag (reported as ``nan_check_lag_max``) for a sync-free
    #: step path. 0 disables the sentinel.
    nan_check_every: int = 1
    #: hand the orbax write + meta/LATEST renames to a background writer
    #: thread; the step path only pays a donation-safe device-side
    #: snapshot. Barriers (join + error propagation) happen at the next
    #: save, NaN rollback, preemption and exit, preserving the crash
    #: contract: a crash during the background write still leaves the
    #: previous valid checkpoint restorable.
    async_checkpoints: bool = True
    handle_sigterm: bool = True
    #: keep a crash flight recorder (observability.flightrec) installed
    #: for the run: recent spans + recovery events, flushed atomically
    #: to flight_<instance>.json in checkpoint_dir on SIGTERM, NaN
    #: rollback, preemption and crash
    flight_recorder: bool = True
    #: persistent XLA compilation cache dir for this run; the
    #: JAX_COMPILATION_CACHE_DIR env var, if set, overrides it (and is
    #: what None falls back to) — a restarted replacement
    #: process pointed at the same dir recompiles ~nothing
    compile_cache_dir: Optional[str] = None
    #: route recovery decisions through the cross-process consensus
    #: layer: "auto" (default) turns it on exactly when
    #: jax.process_count() > 1 and a coordination-service client exists;
    #: True/False force it (False runs a multi-process fleet with
    #: process-LOCAL recovery — only safe when no fault ever fires)
    coordinate: object = "auto"
    #: per-run override for the consensus/barrier deadline (None = the
    #: DL4J_TPU_COLLECTIVE_TIMEOUT_S env var / its default). A consensus
    #: round exceeding it names a lost peer and ends the run
    collective_timeout_s: Optional[float] = None
    #: injectable for tests (real runs sleep through backoff)
    sleep_fn: Callable[[float], None] = time.sleep


@dataclass
class SupervisorResult:
    status: str                    # "completed" | "preempted" | "peer_lost"
    final_step: int
    resumed_from: Optional[str]
    events: List[RecoveryEvent]
    stats: dict
    #: goodput.RunReport for the whole supervised run (None when the
    #: goodput engine is disabled); also saved as run_report.json
    #: (rank-suffixed ``run_report.r<k>.json`` off rank 0) in the
    #: checkpoint dir
    report: Optional[object] = None
    #: status == "peer_lost" detail: {"lost_ranks": [...],
    #: "detection_s": float, "round": str} from the timed-out consensus
    peer_loss: Optional[dict] = None


class TrainingSupervisor:
    """Wraps ``MultiLayerNetwork.fit`` / ``ComputationGraph.fit`` in the
    checkpoint/recovery loop. The core entry point is :meth:`run` (a
    deterministic ``batch_fn(step) -> DataSet`` plus an absolute target
    step — exactly resumable because the data for step *i* never depends
    on how many times the process died); :meth:`fit` adapts the familiar
    (data, labels, epochs, batch_size) surface onto it."""

    def __init__(self, net, config: SupervisorConfig, *, injector=None,
                 stats_collector=None):
        self.net = net
        self.config = config
        self.injector = injector
        self.stats_collector = stats_collector  # TrainingStatsCollector
        self.stats = ResilienceStats()
        self.events: List[RecoveryEvent] = []
        self._preempt_requested = False
        self._last_good: Optional[str] = None
        #: cross-process consensus routing (set per run by
        #: _setup_coordination; False for single-process runs)
        self._coordinated = False
        #: filled when a consensus round named a dead peer
        self.peer_loss: Optional[dict] = None
        #: datapipe.Pipeline being supervised (fit_pipeline): its
        #: state_dict rides in every checkpoint's meta.json and is
        #: restored alongside the net on resume/rollback
        self._pipeline = None
        #: goodput ledger for the active run (reshard annotations land
        #: on the RunReport through it)
        self._ledger = None
        self._lr_scale0 = getattr(net, "_lr_scale", 1.0)
        #: async checkpoint writer state: at most ONE write in flight
        self._ckpt_thread: Optional[threading.Thread] = None
        self._ckpt_pending: Optional[dict] = None
        #: (step, lazy device score) pairs not yet NaN-checked
        self._pending_scores: List[tuple] = []
        os.makedirs(config.checkpoint_dir, exist_ok=True)
        #: crash flight recorder (black box): best-effort — its absence
        #: must never break training
        self.flight = None
        if config.flight_recorder:
            try:
                from deeplearning4j_tpu.observability.flightrec import \
                    install_flight_recorder
                self.flight = install_flight_recorder(
                    dir=config.checkpoint_dir)
            except Exception:
                self.flight = None

    def _flight_flush(self, reason: str, exc=None) -> Optional[str]:
        """Flush the black box (best-effort; returns the artifact path)."""
        if self.flight is None:
            return None
        try:
            return self.flight.flush(reason, exc=exc)
        except Exception:
            return None

    # --------------------------------------------------- cross-process glue
    def _setup_coordination(self):
        """Decide (per run) whether recovery decisions go through the
        consensus layer. Coordinated runs force synchronous checkpoints:
        the save barriers are cross-process collectives and must run on
        the thread making the consensus calls, in the same order on
        every rank."""
        from deeplearning4j_tpu.parallel import distributed as _dist
        cfg = self.config
        if isinstance(cfg.coordinate, bool):
            self._coordinated = cfg.coordinate
        else:
            self._coordinated = _dist.consensus_available()
        if self._coordinated and cfg.async_checkpoints:
            logger.info(
                "multi-process run: checkpoints forced synchronous — the "
                "save barrier is a cross-process collective and must stay "
                "on the consensus thread")
        return self._coordinated

    def _agree(self, code: int, name: str) -> List[int]:
        from deeplearning4j_tpu.parallel import distributed as _dist
        return _dist.agree_decision(
            code, name=name, timeout_s=self.config.collective_timeout_s)

    def _any_process(self, flag: bool, name: str) -> bool:
        return any(self._agree(1 if flag else 0, name))

    def _on_peer_lost(self, exc) -> None:
        """A consensus round named a dead peer: record it, flush the
        black box, and write NOTHING further — the last barriered
        checkpoint (meta.json committed on every rank) is the newest
        restorable state, and any save attempt now would just hang on
        the corpse."""
        self.peer_loss = {
            "lost_ranks": list(getattr(exc, "lost_ranks", [])),
            "detection_s": getattr(exc, "elapsed_s", None),
            "round": getattr(exc, "round_name", ""),
        }
        self._emit("peer_lost", self.net.iteration,
                   f"{exc}", counter="peer_losses")
        self._flight_flush("peer_lost", exc=exc)

    # --------------------------------------------------------------- events
    def _emit(self, kind: str, step: int, detail: str = "",
              counter: Optional[str] = None):
        ev = RecoveryEvent(kind, step, detail)
        self.events.append(ev)
        if counter:
            self.stats.bump(counter)
        if self.flight is not None:
            try:  # the black box sees every recovery event
                self.flight.record_event(kind, step, detail)
            except Exception:
                pass
        logger.info("resilience %s", ev)
        for l in getattr(self.net, "listeners", ()):
            on_recovery = getattr(l, "on_recovery", None)
            if on_recovery is not None:
                on_recovery(self.net, ev)
        return ev

    # ----------------------------------------------------------- checkpoint
    def _step_dir(self, step: int) -> str:
        return os.path.join(self.config.checkpoint_dir, f"step_{step}")

    def _write_latest_pointer(self, path: str):
        # atomic latest-pointer: observers (and a quick resume fast path)
        # read one small file; the rename is the commit point, so the
        # pointer never names a half-written checkpoint. Multi-process:
        # rank 0 only — N processes share the checkpoint dir, and
        # concurrent writers to one .tmp path would interleave
        import jax
        if jax.process_count() > 1 and jax.process_index() != 0:
            return
        tmp = os.path.join(self.config.checkpoint_dir,
                           "." + _LATEST_POINTER + ".tmp")
        with open(tmp, "w") as f:
            f.write(os.path.basename(path))
        os.replace(tmp, os.path.join(self.config.checkpoint_dir,
                                     _LATEST_POINTER))

    def _checkpoint(self, step: int, reason: str, wait: bool = False) -> str:
        """Checkpoint the net's current state. With ``async_checkpoints``
        the step path pays only a donation-safe device-side snapshot
        (``snapshot_for_checkpoint``); the orbax write, meta.json rename
        and LATEST pointer happen on a background writer thread. The
        previous in-flight write is always drained first (one writer at a
        time), and ``wait=True`` (preemption/final saves) drains this one
        too. Writer errors — including injected crashes from the
        faultinject seam, which fires inside the writer — surface at the
        next drain point exactly as a synchronous save's would."""
        from deeplearning4j_tpu.utils.checkpoint import (
            save_checkpoint, snapshot_for_checkpoint)
        cfg = self.config
        tracer = _get_tracer()
        self._drain_checkpoint()
        path = self._step_dir(step)
        # pipeline state is captured HERE on the main thread — in the
        # async path the background writer gets plain data, consistent
        # with the device snapshot taken at the same step boundary
        extra = None
        if self._pipeline is not None:
            extra = {"datapipe": self._pipeline.state_dict()}
        if not cfg.async_checkpoints or self._coordinated:
            with tracer.span("checkpoint_write", step=step, reason=reason):
                save_checkpoint(self.net, path, stats=self.stats_collector,
                                extra_meta=extra)
                self._write_latest_pointer(path)
            self._commit_checkpoint(step, reason, path)
            return path
        with tracer.span("checkpoint_snapshot", step=step):
            snap = snapshot_for_checkpoint(self.net)
        pending = {"step": step, "reason": reason, "path": path,
                   "error": None}

        def write():
            # runs on dl4j-ckpt-writer: the span lands in that thread's
            # trace lane, overlapping the main loop's device_step spans
            try:
                with tracer.span("checkpoint_write", step=step,
                                 reason=reason):
                    save_checkpoint(snap, path, stats=self.stats_collector,
                                    extra_meta=extra)
                    self._write_latest_pointer(path)
            except BaseException as e:  # kept for the drain barrier
                pending["error"] = e

        t = threading.Thread(target=write, name="dl4j-ckpt-writer",
                             daemon=True)
        self._ckpt_pending = pending
        self._ckpt_thread = t
        t.start()
        if wait:
            self._drain_checkpoint()
        return path

    def _commit_checkpoint(self, step: int, reason: str, path: str):
        """Post-write bookkeeping (main thread only): rollback target,
        event/counter, retention GC."""
        self._last_good = path
        self._emit("checkpoint", step, f"{reason} -> {path}",
                   counter="checkpoints")
        self._gc(step)

    def _drain_checkpoint(self, raise_errors: bool = True):
        """Barrier on the in-flight background write (no-op when idle).
        On success the checkpoint becomes the rollback target; on failure
        the stored exception (e.g. an InjectedCrash that fired between
        the tree commit and the meta rename) is re-raised here — the
        async analogue of a synchronous save crashing in place."""
        t, pending = self._ckpt_thread, self._ckpt_pending
        if t is None:
            return
        timeout_s = float(os.environ.get(
            "DL4J_TPU_CKPT_JOIN_TIMEOUT_S", "600"))
        with _get_tracer().span("checkpoint_barrier"):
            t.join(timeout=timeout_s)
        if t.is_alive():
            # the writer wedged (dead filesystem, hung flush): a barrier
            # that never returns would freeze training; fail the drain
            # instead and leave the daemon thread to the interpreter
            err = TimeoutError(
                f"checkpoint writer did not finish within {timeout_s:g}s "
                "(DL4J_TPU_CKPT_JOIN_TIMEOUT_S)")
            self._ckpt_thread = None
            self._ckpt_pending = None
            if raise_errors:
                raise err
            logger.error("async checkpoint write for %s failed: %r",
                         pending["path"], err)
            return
        self._ckpt_thread = None
        self._ckpt_pending = None
        err = pending["error"]
        if err is not None:
            if raise_errors:
                raise err
            logger.error("async checkpoint write for %s failed: %r",
                         pending["path"], err)
            return
        self._commit_checkpoint(pending["step"], pending["reason"],
                                pending["path"])

    def _gc(self, current_step: int):
        """Retention: keep the newest ``keep_checkpoints`` valid steps;
        also sweep partial saves older than the latest valid one (they
        can never be resumed from and would otherwise accumulate one per
        crash). Multi-process: rank 0 only — checkpoints sit in a shared
        directory, and the post-save barrier guarantees no peer is still
        reading a directory rank 0 sweeps."""
        import jax
        if jax.process_count() > 1 and jax.process_index() != 0:
            return
        from deeplearning4j_tpu.utils.checkpoint import (_STEP_DIR,
                                                         is_valid_checkpoint)
        root = self.config.checkpoint_dir
        entries = []
        for name in os.listdir(root):
            m = _STEP_DIR.match(name)
            if m:
                entries.append((int(m.group(1)), os.path.join(root, name)))
        entries.sort()
        valid = [(s, p) for s, p in entries if is_valid_checkpoint(p)]
        keep = {p for _, p in valid[-max(1, self.config.keep_checkpoints):]}
        newest_valid = valid[-1][0] if valid else -1
        removed = 0
        for step, path in entries:
            partial = not is_valid_checkpoint(path)
            if path in keep or (partial and step >= newest_valid):
                continue
            shutil.rmtree(path, ignore_errors=True)
            removed += 1
        if removed:
            self.stats.bump("gc_removed", removed)
            self._emit("gc", current_step,
                       f"removed {removed} old/partial checkpoint(s)")

    def _mesh_kwargs(self) -> dict:
        """Restore kwargs matching the live net's placement, so a meshed
        net's checkpoint leaves land DIRECTLY in their target
        NamedShardings (utils/checkpoint.py schema v2) instead of a host
        round-trip."""
        meshed = getattr(self.net, "_mesh", None)
        if meshed is None:
            return {}
        detail = getattr(self.net, "_mesh_detail", None) or {}
        return dict(mesh=meshed[0], data_axis=meshed[1],
                    model_axis=detail.get("model_axis"),
                    tp_rules=detail.get("tp_rules"))

    def _current_mesh_json(self):
        meshed = getattr(self.net, "_mesh", None)
        if meshed is None:
            return None
        mesh, data_axis = meshed
        detail = getattr(self.net, "_mesh_detail", None) or {}
        return {"axis_names": [str(a) for a in mesh.axis_names],
                "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
                "device_count": int(mesh.size),
                "data_axis": data_axis,
                "model_axis": detail.get("model_axis")}

    def _load_into(self, path: str):
        """Restore ``path`` INTO the existing net object (params, state,
        optimizer state, step/epoch counters) so user references stay
        valid; the compiled step is shape-compatible and is reused.

        Elastic: the checkpoint may have been saved under a DIFFERENT
        mesh/fleet size (schema-v2 layout manifest records the old
        world). Params re-lay onto the live net's mesh automatically;
        a datapipe shard cursor baked for the old fleet is remapped via
        the coverage rule in datapipe/reshard.py, and the transition is
        emitted as a ``reshard`` RecoveryEvent + stamped onto the
        RunReport."""
        from deeplearning4j_tpu.utils.checkpoint import (
            _net_kind, read_checkpoint_layout, read_checkpoint_meta,
            restore_computation_graph, restore_multi_layer_network)
        kw = self._mesh_kwargs()
        if _net_kind(self.net) == "graph":
            restored = restore_computation_graph(path, **kw)
        else:
            restored = restore_multi_layer_network(path, **kw)
        net = self.net
        net.params = restored.params
        net.state = restored.state
        net.opt_state = restored.opt_state
        net.iteration = restored.iteration
        net.epoch = restored.epoch
        self._last_good = path

        layout = read_checkpoint_layout(path)
        old_mesh = (layout or {}).get("mesh")
        new_mesh = self._current_mesh_json()
        old_n = (old_mesh or {}).get("device_count", 1)
        new_n = (new_mesh or {}).get("device_count", 1)
        reshard_detail = None
        if layout is not None and old_n != new_n:
            reshard_detail = {"from_mesh": old_mesh, "to_mesh": new_mesh,
                              "from_process_count":
                                  layout.get("process_count")}

        if self._pipeline is not None:
            meta = read_checkpoint_meta(path)
            if "datapipe" in meta:
                from deeplearning4j_tpu.datapipe.reshard import (
                    remap_for, shard_position)
                dp_state = meta["datapipe"]
                old_pos = shard_position(dp_state)
                try:
                    self._pipeline.load_state_dict(dp_state)
                except ValueError:
                    # shard cursor baked for another fleet size: re-cut
                    # the stream at the coverage rule's low-water mark
                    remapped = remap_for(self._pipeline, dp_state)
                    self._pipeline.load_state_dict(remapped)
                    new_pos = shard_position(remapped)
                    reshard_detail = dict(reshard_detail or {})
                    reshard_detail["datapipe"] = {
                        "from": old_pos and dict(zip("nik", old_pos)),
                        "to": new_pos and dict(zip("nik", new_pos))}
            else:
                logger.warning(
                    "checkpoint %s carries no datapipe state; the pipeline "
                    "keeps its current position", path)

        if reshard_detail is not None:
            self._emit("reshard", net.iteration,
                       f"re-laid onto {new_n} device(s) from a "
                       f"{old_n}-device checkpoint at {path}",
                       counter="reshards")
            if self._ledger is not None:
                self._ledger.annotate(reshard=reshard_detail)

        if self._coordinated:
            # restore barrier: no process races ahead of the orbax
            # commit (into training — or worse, into a rank-0 GC sweep)
            # while a peer is still reading this checkpoint
            from deeplearning4j_tpu.parallel import distributed as _dist
            _dist.barrier("dl4j_restore_done",
                          timeout_s=self.config.collective_timeout_s)

    # ------------------------------------------------------------- stepping
    def request_preemption(self):
        """Ask for a clean stop at the next step boundary (what the
        SIGTERM handler calls; tests and the fault injector call it
        directly)."""
        self._preempt_requested = True

    def _sigterm(self, signum, frame):
        logger.warning("SIGTERM received — will checkpoint and exit at "
                       "the next step boundary")
        # flush the black box NOW: if the sender escalates to SIGKILL
        # before the clean boundary, the post-mortem already exists
        self._flight_flush("sigterm")
        self.request_preemption()

    def _attempt_step(self, ds, step: int):
        """One fit_batch with transient-failure retry + exponential
        backoff. The injector's before_step hook runs inside the retried
        region so injected transient faults exercise this exact path.

        Coordinated runs add a pre-step consensus round per attempt:
        nobody enters the compiled step (whose gradient psum is a
        collective) unless EVERY process is ready, and a transient on
        any rank backs the whole fleet off on the same schedule — the
        single-process retry semantics, made deadlock-free. A failure
        that surfaces INSIDE the collective step cannot be retried in
        lockstep (peers are already mid-psum) and propagates."""
        cfg = self.config
        delay = cfg.backoff_initial_s
        attempt = 0
        while True:
            err = None
            try:
                if self.injector is not None:
                    self.injector.before_step(self, self.net, step)
            except cfg.retry_on as e:
                err = e
            if self._coordinated:
                failed = any(self._agree(0 if err is None else 1, "step"))
            else:
                failed = err is not None
            if not failed:
                try:
                    return self.net.fit_batch(ds)
                except cfg.retry_on as e:
                    if self._coordinated:
                        raise
                    err = e
            attempt += 1
            if attempt > cfg.max_step_retries:
                if err is not None:
                    raise err
                from deeplearning4j_tpu.resilience.faultinject import \
                    TransientStepError
                raise TransientStepError(
                    f"a peer process kept failing step {step} past "
                    f"{cfg.max_step_retries} coordinated retries")
            if err is not None:
                cause = f"{type(err).__name__}: {err}"
            else:
                cause = "peer transient failure"
            self._emit(
                "retry", step,
                f"attempt {attempt}/{cfg.max_step_retries} after "
                f"{cause}; backoff {delay:.3f}s",
                counter="retries")
            cfg.sleep_fn(delay)
            delay = min(delay * cfg.backoff_factor, cfg.backoff_max_s)

    def _flush_nan_checks(self):
        """Materialize every pending lazy score (device sync happens HERE,
        not on the step path) and return the first non-finite
        ``(step, value)``, or None. Detection lag — how many steps ran
        past a score before it was checked — is recorded in
        ``ResilienceStats.nan_check_lag``."""
        pending, self._pending_scores = self._pending_scores, []
        bad = None
        now = self.net.iteration
        for step, score in pending:
            self.stats.note_nan_check_lag(now - (step + 1))
            if bad is None and not math.isfinite(float(score)):
                bad = (step, float(score))
        return bad

    def _agreed_bad(self):
        """The fleet-wide NaN decision. Single-process: just the local
        flush. Coordinated: every process publishes its local verdict
        (0 = clean, step+1 = first bad step) and the agreed outcome is
        the MINIMUM bad step any rank saw — so one poisoned rank rolls
        every rank back to the same checkpoint, in lockstep, even the
        ranks whose local losses were finite. Call sites are
        schedule-aligned (same steps, same due boundaries), so the
        consensus rounds line up by construction."""
        bad = self._flush_nan_checks()
        if not self._coordinated:
            return bad
        code = (bad[0] + 1) if bad is not None else 0
        codes = self._agree(code, "nan")
        hits = [c - 1 for c in codes if c]
        if not hits:
            return None
        step = min(hits)
        score = bad[1] if bad is not None and bad[0] == step else \
            float("nan")
        return (step, score)

    def _rollback(self, step: int, score: float, rollbacks: int):
        cfg = self.config
        # the poisoned trajectory's un-checked scores are moot after the
        # restore, and the writer must be idle before _last_good is read
        self._pending_scores.clear()
        self._drain_checkpoint()
        if rollbacks > cfg.max_nan_rollbacks:
            raise TrainingDivergedError(
                f"loss is non-finite ({score}) at step {step} even after "
                f"{cfg.max_nan_rollbacks} rollback(s) with LR backoff "
                f"x{cfg.nan_lr_backoff} each — giving up rather than "
                "checkpointing poisoned parameters")
        if self._last_good is None:
            raise TrainingDivergedError(
                f"loss is non-finite ({score}) at step {step} and no good "
                "checkpoint exists to roll back to")
        new_scale = getattr(self.net, "_lr_scale", 1.0) * cfg.nan_lr_backoff
        with _get_tracer().span("rollback", step=step):
            self._load_into(self._last_good)
        if hasattr(self.net, "set_lr_scale"):
            self.net.set_lr_scale(new_scale)
        self._emit("rollback", self.net.iteration,
                   f"non-finite loss ({score}) at step {step}; restored "
                   f"{self._last_good}, lr scale now {new_scale:g}",
                   counter="rollbacks")
        self._flight_flush("nan_rollback")

    # ------------------------------------------------------------ main loop
    def run(self, batch_fn: Callable[[int], object],
            target_step: int) -> SupervisorResult:
        """Train until ``net.iteration == target_step`` feeding
        ``batch_fn(step)`` at each step. Resumable: relaunching with the
        same arguments continues from the newest valid checkpoint to the
        same final step."""
        from deeplearning4j_tpu.parallel import distributed as _dist
        from deeplearning4j_tpu.utils.checkpoint import (
            find_latest_checkpoint)
        cfg = self.config
        net = self.net
        resumed_from = None

        _obs_metrics.install_runtime_metrics()
        from deeplearning4j_tpu.compilecache import configure as _cc_configure
        _cc_configure(cfg.compile_cache_dir)  # the env var overrides
        self._setup_coordination()
        # attach (and stay attached after run(): a post-run scrape still
        # reports this job's recovery counters alongside serving/compile
        # series from the same process)
        self.stats.attach_to_registry(
            labels={"job": os.path.basename(
                os.path.normpath(cfg.checkpoint_dir))})
        ledger = _goodput.start_run("resilient_fit", net=net)
        self._ledger = ledger

        if cfg.resume:
            latest = find_latest_checkpoint(cfg.checkpoint_dir)
            if latest is not None:
                with _get_tracer().span("restore"):
                    self._load_into(latest)
                self._emit("resume", net.iteration, f"restored {latest}",
                           counter="resumes")
                resumed_from = latest

        old_handler = None
        use_signal = (cfg.handle_sigterm
                      and threading.current_thread()
                      is threading.main_thread())
        if use_signal:
            old_handler = signal.signal(signal.SIGTERM, self._sigterm)
        rollbacks = 0
        status = "completed"
        try:
            try:
                if (self._last_good is None
                        and net.iteration < target_step):
                    # baseline save: the NaN sentinel needs a rollback
                    # target from the very first step, and a crash before
                    # the first periodic save must not lose the (possibly
                    # expensive) initialization
                    self._checkpoint(net.iteration, "baseline")

                while True:
                    if self._coordinated:
                        # one consensus round per loop pass: SIGTERM (or
                        # an injected preempt) on ANY rank stops every
                        # rank at this same step boundary
                        if self._any_process(self._preempt_requested,
                                             "preempt"):
                            self._preempt_requested = True
                    if self._preempt_requested:
                        status = "preempted"
                        break
                    if net.iteration >= target_step:
                        # tail flush: the last chunk of lazy scores may
                        # hold poison — a rollback rewinds iteration and
                        # re-enters
                        bad = self._agreed_bad()
                        if bad is not None:
                            rollbacks += 1
                            self._rollback(bad[0], bad[1], rollbacks)
                            continue
                        break
                    step = net.iteration
                    score = self._attempt_step(batch_fn(step), step)
                    if cfg.nan_check_every > 0:
                        self._pending_scores.append((step, score))
                    due_check = (cfg.nan_check_every > 0
                                 and net.iteration % cfg.nan_check_every
                                 == 0)
                    due_ckpt = (net.iteration % cfg.checkpoint_every_steps
                                == 0 and net.iteration < target_step)
                    if (due_check or due_ckpt) and self._pending_scores:
                        # every score up to here is verified finite
                        # BEFORE a snapshot is taken: poison is never
                        # checkpointed, even with a lagging
                        # (nan_check_every > 1) sentinel
                        bad = self._agreed_bad()
                        if bad is not None:
                            rollbacks += 1
                            self._rollback(bad[0], bad[1], rollbacks)
                            continue
                    if due_ckpt:
                        self._checkpoint(net.iteration, "periodic")

                if status == "preempted":
                    bad = self._agreed_bad()
                    if bad is not None:
                        # never checkpoint poison, even on the way out
                        rollbacks += 1
                        self._rollback(bad[0], bad[1], rollbacks)
                    self._checkpoint(net.iteration, "preemption",
                                     wait=True)
                    self._emit("preempt", net.iteration,
                               f"clean exit at step {net.iteration} of "
                               f"{target_step}", counter="preemptions")
                    self._flight_flush("preemption")
                else:
                    self._drain_checkpoint()  # settle _last_good first
                    if self._last_good != self._step_dir(net.iteration):
                        self._checkpoint(net.iteration, "final", wait=True)
            except _dist.PeerLostError as e:
                # a peer died mid-run: flush the post-mortem, write NO
                # partial checkpoint (any save barrier would hang on the
                # corpse; the meta.json invariant keeps half-saves
                # non-restorable), exit with a distinct status for the
                # launcher
                status = "peer_lost"
                self._on_peer_lost(e)
        finally:
            if use_signal:
                signal.signal(signal.SIGTERM, old_handler)
            # exit barrier: when an exception is already propagating the
            # writer's own error must not mask it — join + log only. On
            # clean paths the writer was drained above (wait=True saves),
            # so this is a no-op.
            self._drain_checkpoint(raise_errors=False)
            if sys.exc_info()[0] is not None:
                # exception path: still close the ledger (end_run is
                # idempotent, so the clean-path call below stays a no-op)
                # and flush the black box — THE post-mortem artifact
                self._flight_flush("exception", exc=sys.exc_info()[1])
                _goodput.end_run(ledger, status="failed")

        report = _goodput.end_run(
            ledger, status=status, save_to=self._report_path())
        return SupervisorResult(
            status=status, final_step=net.iteration,
            resumed_from=resumed_from, events=list(self.events),
            stats=self.stats.snapshot(), report=report,
            peer_loss=self.peer_loss)

    def _report_path(self) -> str:
        """``run_report.json`` — rank-suffixed (``run_report.r<k>.json``)
        off rank 0, so N processes sharing one checkpoint dir stop
        clobbering each other's reports."""
        from deeplearning4j_tpu.observability.distributed import rank_suffix
        return os.path.join(self.config.checkpoint_dir,
                            f"run_report{rank_suffix()}.json")

    # ------------------------------------------------------- pipeline loop
    def fit_pipeline(self, pipeline, *, epochs: int = 1) -> SupervisorResult:
        """Supervise training over a ``datapipe.Pipeline`` — the
        streaming-source twin of :meth:`run`. The pipeline's
        ``state_dict()`` rides in every checkpoint's ``meta.json``
        (captured at the same step boundary as the device snapshot), so
        resume and NaN rollback restore DATA position — epoch, source
        cursor, shuffle RNG + window, partial batch buffers, prefetched
        batches — alongside the parameters: a killed-and-relaunched run
        consumes the exact record sequence an uninterrupted one would,
        and final params are bit-identical even from a shuffled or
        streaming source. Completion is data-driven (the stream runs out
        of epochs) rather than an absolute target step."""
        cfg = self.config
        net = self.net
        self._pipeline = pipeline
        resumed_from = None

        from deeplearning4j_tpu.parallel import distributed as _dist
        from deeplearning4j_tpu.utils.checkpoint import (
            find_latest_checkpoint)
        _obs_metrics.install_runtime_metrics()
        from deeplearning4j_tpu.compilecache import configure as _cc_configure
        _cc_configure(cfg.compile_cache_dir)  # the env var overrides
        self._setup_coordination()
        self.stats.attach_to_registry(
            labels={"job": os.path.basename(
                os.path.normpath(cfg.checkpoint_dir))})
        ledger = _goodput.start_run("resilient_fit", net=net)
        self._ledger = ledger

        if cfg.resume:
            latest = find_latest_checkpoint(cfg.checkpoint_dir)
            if latest is not None:
                with _get_tracer().span("restore"):
                    self._load_into(latest)
                self._emit("resume", net.iteration,
                           f"restored {latest} (datapipe epoch "
                           f"{pipeline.epoch})", counter="resumes")
                resumed_from = latest

        old_handler = None
        use_signal = (cfg.handle_sigterm
                      and threading.current_thread()
                      is threading.main_thread())
        if use_signal:
            old_handler = signal.signal(signal.SIGTERM, self._sigterm)
        stream = None

        def invalidate_stream():
            # close the live generator chain FIRST (stops prefetch
            # workers mid-pull) so a restore never races a worker still
            # mutating upstream stage state
            nonlocal stream
            if stream is not None:
                stream.close()
                stream = None

        rollbacks = 0
        status = "completed"
        try:
            try:
                if self._last_good is None:
                    # baseline save: rollback target from the very first
                    # step, now including the pipeline's start-of-run
                    # state
                    self._checkpoint(net.iteration, "baseline")

                while True:
                    if self._coordinated:
                        # preemption consensus BEFORE pulling a batch:
                        # the final checkpoint's data cursor must not
                        # have consumed a record nobody trained on
                        if self._any_process(self._preempt_requested,
                                             "preempt"):
                            self._preempt_requested = True
                    if self._preempt_requested:
                        status = "preempted"
                        break
                    if stream is None:
                        stream = pipeline.stream(epochs)
                    ds = next(stream, None)
                    if self._coordinated:
                        # epoch-end is a fleet decision: the first shard
                        # to run dry ends the epoch everywhere (peers
                        # drop their surplus — mirroring LocalSGD's
                        # windowed agreement), because a lone finisher
                        # heading for the exit barrier while others keep
                        # training is a deadlock
                        exhausted = self._any_process(ds is None, "data")
                    else:
                        exhausted = ds is None
                    if exhausted:
                        # stream exhausted — but the lazy-score tail may
                        # hold poison; a rollback rewinds data position
                        # too and re-enters the loop with a rebuilt
                        # stream
                        bad = self._agreed_bad()
                        if bad is not None:
                            rollbacks += 1
                            invalidate_stream()
                            self._rollback(bad[0], bad[1], rollbacks)
                            continue
                        break
                    step = net.iteration
                    score = self._attempt_step(ds, step)
                    if cfg.nan_check_every > 0:
                        self._pending_scores.append((step, score))
                    due_check = (cfg.nan_check_every > 0
                                 and net.iteration % cfg.nan_check_every
                                 == 0)
                    due_ckpt = (net.iteration % cfg.checkpoint_every_steps
                                == 0)
                    if (due_check or due_ckpt) and self._pending_scores:
                        bad = self._agreed_bad()
                        if bad is not None:
                            rollbacks += 1
                            invalidate_stream()
                            self._rollback(bad[0], bad[1], rollbacks)
                            continue
                    if due_ckpt:
                        self._checkpoint(net.iteration, "periodic")

                if status == "preempted":
                    bad = self._agreed_bad()
                    if bad is not None:
                        rollbacks += 1
                        invalidate_stream()
                        self._rollback(bad[0], bad[1], rollbacks)
                    # park the prefetch workers so the saved pipeline
                    # state is the final word on data position
                    invalidate_stream()
                    self._checkpoint(net.iteration, "preemption",
                                     wait=True)
                    self._emit("preempt", net.iteration,
                               f"clean exit at step {net.iteration} "
                               f"(datapipe epoch {pipeline.epoch} of "
                               f"{epochs})", counter="preemptions")
                    self._flight_flush("preemption")
                else:
                    self._drain_checkpoint()  # settle _last_good first
                    if self._last_good != self._step_dir(net.iteration):
                        self._checkpoint(net.iteration, "final", wait=True)
            except _dist.PeerLostError as e:
                status = "peer_lost"
                self._on_peer_lost(e)
        finally:
            if use_signal:
                signal.signal(signal.SIGTERM, old_handler)
            invalidate_stream()
            # the pipeline reports only while consumed: detach its
            # collector so back-to-back runs over fresh pipeline objects
            # don't accumulate stale families in the global registry
            pipeline.stats.detach_from_registry()
            self._drain_checkpoint(raise_errors=False)
            if sys.exc_info()[0] is not None:
                self._flight_flush("exception", exc=sys.exc_info()[1])
                _goodput.end_run(ledger, status="failed")

        report = _goodput.end_run(
            ledger, status=status, save_to=self._report_path())
        return SupervisorResult(
            status=status, final_step=net.iteration,
            resumed_from=resumed_from, events=list(self.events),
            stats=self.stats.snapshot(), report=report,
            peer_loss=self.peer_loss)

    # ----------------------------------------------------------- fit facade
    def fit(self, data, labels=None, *, epochs: int = 1,
            batch_size: int = 32) -> SupervisorResult:
        """The ``fit``-shaped entry: materializes the batch sequence and
        supervises to the absolute step ``epochs * len(batches)`` —
        absolute so a killed-and-relaunched run lands on the SAME final
        step count as an uninterrupted one. A ``datapipe.Pipeline``
        dispatches to :meth:`fit_pipeline` instead (streaming, never
        materialized; data position checkpointed)."""
        from deeplearning4j_tpu.datapipe.core import Pipeline
        if isinstance(data, Pipeline):
            return self.fit_pipeline(data, epochs=epochs)
        batches = _materialize_batches(data, labels, batch_size)
        if not batches:
            raise ValueError("no training batches")
        target = epochs * len(batches)
        return self.run(lambda step: batches[step % len(batches)], target)


def _materialize_batches(data, labels, batch_size):
    """(data, labels) | DataSet | MultiDataSet | iterator -> list of
    batches. Materialized so batch_fn(step) is deterministic across
    restarts (resumability beats streaming here; for out-of-core data
    pass a deterministic batch_fn to run() directly)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
    from deeplearning4j_tpu.datasets.iterator import (ArrayDataSetIterator,
                                                      DataSetIterator)
    if isinstance(data, (DataSet, MultiDataSet)):
        return [data]
    if isinstance(data, DataSetIterator):
        batches = list(data)
        data.reset()
        return batches
    return list(ArrayDataSetIterator(data, labels, batch_size=batch_size))


def resilient_fit(net, data, labels=None, *, checkpoint_dir: str,
                  epochs: int = 1, batch_size: int = 32, injector=None,
                  stats_collector=None, **config_kw) -> SupervisorResult:
    """One-call supervised training: ``resilient_fit(net, x, y,
    checkpoint_dir=...)`` trains with checkpoint/resume, retry, NaN
    rollback and preemption handling. ``config_kw`` feeds
    SupervisorConfig (checkpoint_every_steps, keep_checkpoints, ...)."""
    cfg = SupervisorConfig(checkpoint_dir=checkpoint_dir, **config_kw)
    sup = TrainingSupervisor(net, cfg, injector=injector,
                             stats_collector=stats_collector)
    return sup.fit(data, labels, epochs=epochs, batch_size=batch_size)
