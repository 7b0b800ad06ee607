"""Multi-host (multi-process) distributed training — the DP-2 tier.

Replaces the reference's Spark parameter-averaging scaleout
(dl4j-spark/.../paramavg/ParameterAveragingTrainingMaster.java:358
executeTraining: broadcast params -> workers fit local minibatches ->
RDD.aggregate sums -> divide -> rebroadcast, §3.4) with the TPU-native
single-controller model (SURVEY.md §5.8): every process calls
``initialize()`` (jax.distributed), the device mesh spans ALL processes'
devices, and the SAME jitted train step runs SPMD everywhere — XLA lowers
the gradient all-reduce onto ICI within a host and DCN across hosts. There
is no driver, no broadcast step, and no parameter copy per round: the
"averaging" is the gradient psum inside the compiled step, every step.

Data feeding: each process supplies its LOCAL slice of the global batch;
``parallel.data_parallel.shard_batch`` assembles the process-local arrays
into one global sharded Array
(jax.make_array_from_process_local_data — the RDD-partition analogue) —
the meshed networks route through it automatically.

The exact-equivalence contract (TestCompareParameterAveragingSparkVs
SingleMachine.java analogue) is pinned by
tests/test_multihost.py: 2 spawned processes x 4 virtual CPU devices
training on disjoint batch halves must produce params bit-identical to
each other AND matching a single-process run on the full batch.
"""

from __future__ import annotations

import logging
import os
import threading
import time
import warnings

import jax
import numpy as np

logger = logging.getLogger("deeplearning4j_tpu")

#: default for DL4J_TPU_COLLECTIVE_TIMEOUT_S — how long a consensus
#: round waits for every peer before declaring one lost
DEFAULT_COLLECTIVE_TIMEOUT_S = 60.0


class CollectiveTimeoutError(RuntimeError):
    """A cross-process consensus call did not complete within the
    collective timeout (``DL4J_TPU_COLLECTIVE_TIMEOUT_S``)."""


class PeerLostError(CollectiveTimeoutError):
    """A consensus round timed out waiting for specific peer processes
    — they are presumed dead (crashed, SIGKILLed, or hung past the
    collective timeout). The supervisor turns this into a
    ``peer_lost`` exit; the fleet launcher relaunches on it."""

    def __init__(self, msg: str, *, lost_ranks=(), elapsed_s=None,
                 round_name: str = ""):
        super().__init__(msg)
        self.lost_ranks = list(lost_ranks)
        self.elapsed_s = elapsed_s
        self.round_name = round_name


def collective_timeout_s() -> float:
    """The consensus/barrier deadline: env ``DL4J_TPU_COLLECTIVE_TIMEOUT_S``
    (seconds), else :data:`DEFAULT_COLLECTIVE_TIMEOUT_S`."""
    raw = os.environ.get("DL4J_TPU_COLLECTIVE_TIMEOUT_S")
    if raw:
        try:
            return max(0.1, float(raw))
        except ValueError:
            logger.warning("ignoring malformed "
                           "DL4J_TPU_COLLECTIVE_TIMEOUT_S=%r", raw)
    return DEFAULT_COLLECTIVE_TIMEOUT_S


def _client():
    """The jax.distributed coordination-service client (the KV store /
    barrier endpoint every process holds once ``initialize`` ran), or
    None outside a multi-process runtime."""
    try:
        from jax._src import distributed as _jdist
        return _jdist.global_state.client
    except Exception:
        return None


def _runtime_up() -> bool:
    """True once this process is attached to a jax.distributed runtime
    (client on workers; coordinator-owning process 0 also has one)."""
    try:
        from jax._src import distributed as _jdist
        state = _jdist.global_state
        return state.client is not None or state.service is not None
    except Exception:
        return False


def consensus_available() -> bool:
    """True when the consensus layer can actually allgather: more than
    one process AND a live coordination-service client to do it over."""
    return jax.process_count() > 1 and _client() is not None


# Round counters: every process makes the SAME sequence of consensus
# calls per name (SPMD discipline — the supervisor's recovery decisions
# are schedule-aligned), so a per-process monotonic counter yields the
# same round number everywhere without any extra coordination.
_round_lock = threading.Lock()
_rounds: dict = {}


def _next_round(name: str) -> int:
    with _round_lock:
        n = _rounds.get(name, 0)
        _rounds[name] = n + 1
        return n


def _reset_rounds() -> None:
    """Tests only: forget round counters (a fresh fake cluster)."""
    with _round_lock:
        _rounds.clear()


def _key_prefix() -> str:
    # incarnation-scoped so a relaunched fleet reusing one coordinator
    # never collides with a previous launch's keys
    return os.environ.get("DL4J_TPU_INCARNATION", "0")


def agree_decision(code: int, *, name: str = "decision",
                   timeout_s: float | None = None) -> list[int]:
    """Allgather one tiny integer recovery code across every process.

    The consensus primitive the multi-process supervisor routes every
    recovery decision through: each process publishes ``code`` to the
    coordination-service KV store and blocking-reads every peer's,
    returning ``[code_0, ..., code_{n-1}]`` (identical on every
    process). Unlike an XLA collective (``process_allgather``), a dead
    peer cannot hang this forever: a read that exceeds the collective
    timeout raises :class:`PeerLostError` naming the missing rank(s).

    Single-process: returns ``[code]`` without touching any runtime."""
    code = int(code)
    count = jax.process_count()
    if count == 1:
        return [code]
    client = _client()
    if client is None:
        raise RuntimeError(
            "agree_decision needs the jax.distributed coordination "
            "service — call parallel.distributed.initialize() first")
    if timeout_s is None:
        timeout_s = collective_timeout_s()
    rank = jax.process_index()
    rnd = _next_round(name)
    base = f"dl4j/agree/{_key_prefix()}/{name}/{rnd}"
    client.key_value_set(f"{base}/{rank}", str(code))
    codes: list = []
    lost: list = []
    t0 = time.monotonic()
    deadline = t0 + timeout_s
    for peer in range(count):
        remaining_ms = max(100, int((deadline - time.monotonic()) * 1000))
        try:
            v = client.blocking_key_value_get(f"{base}/{peer}",
                                              remaining_ms)
        except Exception:
            # jaxlib surfaces the KV deadline as XlaRuntimeError
            # DEADLINE_EXCEEDED; any failure to hear from the peer
            # within budget is treated the same — presumed lost
            lost.append(peer)
            codes.append(None)
        else:
            codes.append(int(v))
    if lost:
        elapsed = time.monotonic() - t0
        raise PeerLostError(
            f"no decision from process(es) {lost} for consensus round "
            f"{name!r}#{rnd} within {timeout_s:.1f}s (waited "
            f"{elapsed:.1f}s) — peer(s) presumed lost",
            lost_ranks=lost, elapsed_s=elapsed, round_name=name)
    if rnd >= 2:
        # GC our own key from two rounds back: every peer reaching round
        # rnd has finished reading round rnd-1, hence rnd-2 long before
        try:
            client.key_value_delete(f"dl4j/agree/{_key_prefix()}/{name}/"
                                    f"{rnd - 2}/{rank}")
        except Exception:
            pass
    return codes


def any_process(flag: bool, *, name: str = "flag",
                timeout_s: float | None = None) -> bool:
    """True iff ``flag`` is truthy on ANY process (the broadcast-OR the
    supervisor uses for preemption: one SIGTERM anywhere stops the whole
    fleet at the same step boundary)."""
    return any(agree_decision(1 if flag else 0, name=name,
                              timeout_s=timeout_s))


def barrier(name: str, *, timeout_s: float | None = None) -> None:
    """Cross-process barrier with a deadline. Uses the coordination
    service's native barrier (timeout-capable — a dead peer raises
    :class:`PeerLostError` instead of hanging forever); falls back to
    ``sync_global_devices`` (an XLA collective, no timeout) when no
    client exists. No-op single-process."""
    if jax.process_count() == 1:
        return
    client = _client()
    if client is None:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)
        return
    if timeout_s is None:
        timeout_s = collective_timeout_s()
    rnd = _next_round(f"barrier/{name}")
    barrier_id = f"dl4j/{_key_prefix()}/barrier/{name}/{rnd}"
    t0 = time.monotonic()
    try:
        client.wait_at_barrier(barrier_id, int(timeout_s * 1000))
    except Exception as e:
        elapsed = time.monotonic() - t0
        raise PeerLostError(
            f"barrier {name!r}#{rnd} did not complete within "
            f"{timeout_s:.1f}s ({e}) — peer presumed lost",
            elapsed_s=elapsed, round_name=name) from e


_ALREADY_UP_WARNED = False


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None):
    """Bring up the multi-process runtime (jax.distributed.initialize).

    Arguments default to the standard env vars (JAX_COORDINATOR_ADDRESS,
    JAX_NUM_PROCESSES, JAX_PROCESS_ID) so launchers can stay declarative;
    on TPU pods with no args at all, jax autodetects the topology.

    Idempotent: when the runtime is already up (a second call —
    ``jax.distributed.initialize`` itself would raise), warns once and
    returns :func:`process_info` for the existing cluster."""
    global _ALREADY_UP_WARNED
    if _runtime_up():
        if not _ALREADY_UP_WARNED:
            _ALREADY_UP_WARNED = True
            warnings.warn(
                "parallel.distributed.initialize(): the jax.distributed "
                "runtime is already up; returning the existing cluster's "
                "process_info()", RuntimeWarning, stacklevel=2)
        return process_info()
    kwargs = {}
    if coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        kwargs["coordinator_address"] = (
            coordinator_address or os.environ["JAX_COORDINATOR_ADDRESS"])
    if num_processes is not None or os.environ.get("JAX_NUM_PROCESSES"):
        kwargs["num_processes"] = int(
            num_processes if num_processes is not None
            else os.environ["JAX_NUM_PROCESSES"])
    if process_id is not None or os.environ.get("JAX_PROCESS_ID"):
        kwargs["process_id"] = int(
            process_id if process_id is not None
            else os.environ["JAX_PROCESS_ID"])
    jax.distributed.initialize(**kwargs)
    return process_info()


def process_info():
    return {"process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "local_devices": len(jax.local_devices()),
            "global_devices": len(jax.devices())}


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def sync_check(tree) -> bool:
    """Cross-process agreement check: True iff every process holds
    bit-identical leaves (the params-stay-in-sync assertion the Spark
    master enforced structurally by rebroadcasting; here it is a test/
    debug utility because SPMD keeps them in sync by construction)."""
    from jax.experimental import multihost_utils
    leaves = jax.tree_util.tree_leaves(tree)
    ok = True
    for leaf in leaves:
        arr = np.asarray(jax.device_get(leaf))
        gathered = multihost_utils.process_allgather(arr)
        ok = ok and bool(np.all(gathered == gathered[0]))
    return ok


class MultiProcessLocalSGD:
    """DP-3 substitution: the reference's asynchronous Aeron parameter
    server (deeplearning4j-scaleout-parallelwrapper-parameter-server/...
    /ParameterServerParallelWrapper.java:161 spawns ParameterServerNode,
    :208 workers push/pull over UDP).

    Design decision (documented substitution): asynchronous push/pull
    updates do not map onto the TPU SPMD model — there is no server to
    push to, and XLA programs are bulk-synchronous. The TPU-native
    equivalent with the same systems goal (decouple workers from
    lock-step gradient exchange, trade staleness for communication) is
    communication-avoiding LOCAL SGD: each process trains independently
    on its local data for ``averaging_frequency`` steps with NO
    cross-process traffic, then parameters (and optionally updater state)
    are averaged across processes over DCN. averaging_frequency=1
    degenerates to synchronous parameter averaging; larger values give
    the parameter-server-style reduced communication pattern.

    The net must NOT be meshed across processes (each process holds its
    own replica — the PS-worker analogue).
    """

    def __init__(self, net, averaging_frequency: int = 1,
                 average_updaters: bool = True):
        if averaging_frequency < 1:
            raise ValueError("averaging_frequency must be >= 1")
        self.net = net
        self.averaging_frequency = averaging_frequency
        self.average_updaters = average_updaters
        self._local_steps = 0
        #: surplus local batches the windowed agreement dropped when the
        #: global-minimum count ended an epoch (uneven shards lose data
        #: silently otherwise — also counted into the
        #: dl4j_localsgd_dropped_batches_total metric)
        self.dropped_batches = 0
        # per-phase EventStats (ParameterAveragingTrainingMasterStats
        # parity — parallel/stats.py): fit / average timings per worker
        from deeplearning4j_tpu.parallel.stats import TrainingStatsCollector
        self.stats = TrainingStatsCollector(
            worker_id=f"worker_{jax.process_index()}")

    def _average_tree(self, tree):
        from jax.experimental import multihost_utils

        def avg(leaf):
            gathered = multihost_utils.process_allgather(
                np.asarray(jax.device_get(leaf)))
            return jax.numpy.asarray(
                np.mean(gathered, axis=0, dtype=np.float64).astype(
                    np.asarray(leaf).dtype))

        return jax.tree_util.tree_map(avg, tree)

    def average_now(self):
        """Cross-process parameter (+ updater-state) average — the
        processResults aggregate/divide step
        (ParameterAveragingTrainingMaster.java:851-877), as one DCN
        all-gather + mean instead of a driver round-trip."""
        with self.stats.time_phase("average"):
            self.net.params = self._average_tree(self.net.params)
            if self.average_updaters and self.net.opt_state is not None:
                self.net.opt_state = self._average_tree(self.net.opt_state)
        return self.net

    def fit_batch(self, ds):
        """One local step; averages every ``averaging_frequency`` steps.
        NOTE: the periodic average is a COLLECTIVE — when driving
        fit_batch directly, every process must take the same number of
        steps or the allgather deadlocks. ``fit`` handles uneven local
        iterators itself."""
        with self.stats.time_phase("fit"):
            score = self.net.fit_batch(ds)
            # the step is async-dispatched; pull the score so the timed
            # span covers real device work, not queue submission
            float(score)
        self._local_steps += 1
        if self._local_steps % self.averaging_frequency == 0:
            self.average_now()
        return score

    def _note_dropped(self, n: int):
        """Account surplus batches the agreement dropped: metric +
        one warning per epoch end (data loss must be observable, not
        silent)."""
        self.dropped_batches += n
        try:
            from deeplearning4j_tpu.observability.metrics import \
                get_registry
            get_registry().counter(
                "dl4j_localsgd_dropped_batches_total",
                "Surplus local batches dropped when the global-minimum "
                "count ended a LocalSGD epoch (uneven shards)").inc(n)
        except Exception:
            pass
        logger.warning(
            "MultiProcessLocalSGD.fit: dropping %d surplus local "
            "batch(es) on process %d — a peer ran out of data first "
            "(uneven shards; %d dropped total this trainer)",
            n, jax.process_index(), self.dropped_batches)

    def fit(self, iterator, *, epochs: int = 1, window: int | None = None):
        """Epoch loop over a LOCAL iterator. Processes may hold uneven
        batch counts (dataset not divisible by process count), and the
        agreed step count drives a COLLECTIVE schedule — so the counts
        must reflect what iteration actually yields (a sized iterator
        whose __len__ over-reports would deadlock the averaging allgather
        on one host).

        The agreement is WINDOWED: each round every process pulls up to
        ``window`` batches into a bounded buffer, the available counts are
        allgathered, the global minimum is trained on everywhere, and the
        leftovers carry into the next round. Memory is bounded by
        ``window`` batches (streaming epoch-scale data works), and the
        total step count per epoch equals the global-minimum batch count —
        identical to whole-epoch agreement. ``window`` defaults to
        max(averaging_frequency, 16)."""
        from jax.experimental import multihost_utils
        if window is None:
            window = max(self.averaging_frequency, 16)
        if window < 1:
            raise ValueError("window must be >= 1")
        for _ in range(epochs):
            it = iter(iterator)
            pending: list = []
            exhausted = False
            while True:
                while len(pending) < window and not exhausted:
                    try:
                        pending.append(next(it))
                    except StopIteration:
                        exhausted = True
                counts = multihost_utils.process_allgather(
                    np.asarray(len(pending)))
                n = int(np.min(counts))
                if n == 0:
                    # some process is out of data: epoch over everywhere
                    # (its peers drop their surplus, as the reference's
                    # balanced repartition would have prevented upstream)
                    if pending:
                        self._note_dropped(len(pending))
                    break
                for ds in pending[:n]:
                    self.fit_batch(ds)
                pending = pending[n:]
            if hasattr(iterator, "reset"):
                iterator.reset()
        if self._local_steps % self.averaging_frequency != 0:
            self.average_now()
        return self.net
