"""Continuous-batching HTTP inference server.

Parity surface: DL4jServeRouteBuilder.java:27,64 (deserialize record ->
``Model.output()`` -> publish), grown into a production serving
runtime. The seed design serialized every request under a global lock —
one forward per request, accelerator idle between dispatches. This
version decouples the HTTP threads from the device entirely:

- HTTP handlers *enqueue* tickets into a bounded queue; a single device
  thread (serving/batcher.py) coalesces whatever is pending — across
  requests — into ONE padded power-of-two bucket forward, then scatters
  result rows back to each request's future.
- ``start()`` warm-up precompiles the whole bucket ladder (when the
  model's input row shape is inferable or given via ``input_shapes``),
  so no live request pays the first-compile stall.
- Admission control: a full queue answers 503 + ``Retry-After`` instead
  of growing without bound; ``stop()`` drains accepted work first.
- ``/metrics`` (serving/metrics.py): request/row counters, p50/p95/p99
  latency, executed-batch-size histogram, queue depth, coalesce ratio,
  compile count (= ``len(shapes_seen)``).

Works for MultiLayerNetwork (single ``features`` array) and
ComputationGraph (list under ``inputs``; multi-output replies are
lists). Multi-input requests coalesce only within the same input
arity/row-shape group.

Endpoints:
- ``POST /predict``  {"features": [[...]]} or {"inputs": [[[...]], ...]}
  -> {"predictions": ...}
- ``POST /decode``   (when built with ``decode_engine=``) the sessionful
  cross-host decode protocol: {"op": "prefill"|"step"|"close", "sid":
  ..., "ids": [history], "token": t} -> {"logits": [...]} — a ``step``
  for an unknown sid re-prefills from the carried history, the seam a
  FrontDoorRouter fails sessions over on (serving/router.py)
- ``GET /healthz``   liveness + model summary sizes
- ``GET /metrics``   ServingStats snapshot (JSON); with
  ``Accept: text/plain`` (or ``?format=prometheus``) the unified
  registry in Prometheus text exposition instead
"""

from __future__ import annotations

import json
import os
import threading
import time
from concurrent.futures import TimeoutError as _FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from deeplearning4j_tpu.observability import goodput as _goodput
from deeplearning4j_tpu.observability import metrics as _obs_metrics
from deeplearning4j_tpu.observability import trace as _obs_trace
from deeplearning4j_tpu.serving.batcher import (BatcherDeadError,
                                                MicroBatcher, QueueFullError,
                                                next_bucket)
from deeplearning4j_tpu.serving.fleet import ReplicaSet
from deeplearning4j_tpu.serving.metrics import ServingStats

_ = MicroBatcher  # re-exported (seed name); replicas are built by ReplicaSet

_next_bucket = next_bucket  # back-compat alias (seed name)


class DeadlineExceededError(RuntimeError):
    """The per-request deadline (``request_timeout_s``) expired before
    the device produced a result — mapped to HTTP 504."""


class UnknownSessionError(KeyError):
    """A decode op referenced a session this host does not hold and the
    request carried no token history to recover it from — mapped to
    HTTP 404 (the router retries elsewhere or surfaces it; a plain 400
    would read as a malformed request rather than a routing miss)."""

    def __str__(self):
        # KeyError.__str__ repr()s its arg; error payloads want prose
        return self.args[0] if self.args else ""


class _ServingHTTPServer(ThreadingHTTPServer):
    # default listen backlog is 5 — a 64-client closed-loop burst gets
    # connection resets before a single handler thread even spawns
    request_queue_size = 128


class ModelServer:
    def __init__(self, net, host: str = "127.0.0.1", port: int = 9500,
                 max_batch: int = 1024, batch_window_ms: float = 2.0,
                 max_queue: int = 1024, warmup: bool = True,
                 input_shapes=None, request_timeout_s: float = 300.0,
                 compute_dtype=None, replicas: int = 1, mesh=None,
                 model_axis: str = "model", data_axis=None, tp_rules=None,
                 compile_cache_dir=None, aot_manifest=None,
                 tuning_report=None, decode_engine=None,
                 push_url=None, push_interval_s: float = 2.0,
                 slos=None, scheduler=None):
        from deeplearning4j_tpu.compilecache import cache as _ccache
        # Cold-start engine (SERVING.md "Cold start & AOT"):
        # - compile_cache_dir (or $JAX_COMPILATION_CACHE_DIR, which
        #   overrides it) activates the
        #   persistent compilation cache, so a second boot of the same
        #   config deserializes executables instead of compiling;
        # - aot_manifest names (or True auto-locates, in the cache dir)
        #   the scripts/precompile.py receipt validated at start() —
        #   mismatch warns and falls back to lazy compile;
        # - tuning_report loads an autotuned (max_batch, batch_window_ms)
        #   from compilecache.autotune, overriding the defaults.
        self.compile_cache_dir = _ccache.configure(compile_cache_dir)
        self.aot_manifest = aot_manifest
        self.aot_manifest_ok = None  # set by start() when a manifest loads
        if tuning_report is not None:
            from deeplearning4j_tpu.compilecache import autotune as _at
            tuned = _at.load_tuned(tuning_report)
            max_batch = tuned["max_batch"]
            batch_window_ms = tuned["batch_window_ms"]
            self.tuned_config = tuned
        else:
            self.tuned_config = None
        self.net = net
        self.host = host
        self.port = port
        self.max_batch = max_batch
        self.warmup = warmup
        self.input_shapes = input_shapes
        self.request_timeout_s = float(request_timeout_s)
        self._httpd = None
        self._thread = None
        self._ledger = None
        self._fleet_collector = None
        self._decode_collector = None
        self.run_report = None  # goodput RunReport, set by stop()
        self.warmup_s = None    # warm-up ladder wall time, set by start()
        self._is_graph = hasattr(net, "conf") and hasattr(
            net.conf, "network_inputs")
        # Serving precision contract (PRECISION.md / SERVING.md):
        # compute_dtype=None serves with the net's own policy and keeps
        # the bit-identity contract (coalesced rows == row-at-a-time
        # rows, bit for bit). An explicit compute_dtype (e.g. "bfloat16")
        # serves through a shadow view of the SAME params under a
        # replaced policy — outputs then carry a numeric-tolerance
        # contract vs the f32 forward, not bit-identity.
        self.compute_dtype = compute_dtype
        self._serving_net = None
        if (compute_dtype is not None and compute_dtype
                != net.conf.global_conf.dtype.compute_dtype):
            self._serving_net = self._build_serving_net(compute_dtype)
        self.stats = ServingStats()
        # Mesh-parallel serving (SERVING.md "Fleet"): the coalesced
        # bucket forward runs tensor-parallel under shard_map with
        # arithmetic-free boundary collectives — params sharded ONCE
        # here, bit-identity preserved for f32 (parallel/inference.py).
        self.mesh = mesh
        min_batch = 2
        if mesh is not None:
            if self._is_graph:
                raise ValueError(
                    "mesh-parallel serving supports sequential layer "
                    "stacks; serve ComputationGraph models replicated")
            if compute_dtype is not None:
                raise ValueError(
                    "mesh serving is the f32 bit-identity path; combine "
                    "with compute_dtype via a bf16-policy net instead")
            from deeplearning4j_tpu.parallel.inference import (
                build_tp_output_fn)
            forward = build_tp_output_fn(net, mesh, model_axis,
                                         data_axis=data_axis,
                                         rules=tp_rules)
            if data_axis is not None:
                # data-sharded buckets must divide over the data axis;
                # power-of-two buckets >= the axis size always do
                min_batch = max(min_batch, int(mesh.shape[data_axis]))
        else:
            forward = self._device_forward
        # SLO-aware admission (SERVING.md §Traffic engine): on by
        # default with no quotas configured — class watermarks degrade
        # batch first under backpressure while default-class traffic
        # keeps the legacy reject threshold exactly; scheduler=False
        # disables (the bench.py sched_overhead off-arm), an explicit
        # SchedulingCore customizes quotas/watermarks.
        if scheduler is False:
            self.scheduler = None
        elif scheduler is None:
            from deeplearning4j_tpu.scheduling.core import SchedulingCore
            self.scheduler = SchedulingCore()
        else:
            self.scheduler = scheduler
        self._sched_collector = None
        # N batcher workers behind one admission queue (serving/fleet.py)
        # — replicas=1 degenerates to the single-batcher seed behavior
        self._fleet = ReplicaSet(
            forward, int(replicas), max_batch=max_batch,
            batch_window_ms=batch_window_ms, max_queue=max_queue,
            min_batch=min_batch, stats=self.stats,
            scheduler=self.scheduler)
        # every distinct padded batch shape handed to the device (warm-up
        # ladder included) — the compile count is bounded by
        # len(shapes_seen) (asserted by the serving concurrency test);
        # shared across replicas: the ladder compiles per forward
        self.shapes_seen = self._fleet.shapes_seen
        # Cross-host federation (SERVING.md "Cross-host federation"):
        # - decode_engine: a serving.decode.DecodeEngine this host serves
        #   sessionful /decode on. The wire protocol carries the full
        #   token history on every step, so an UNKNOWN sid is recovered
        #   by re-prefill — bit-identical, which is what lets a
        #   front-door router fail a session over onto this host after
        #   its pinned host died.
        # - push_url: a router/UIServer /api/metrics_push endpoint this
        #   host heartbeats its metrics snapshot to (HeartbeatPusher,
        #   retry attempts=3), carrying server_url so the router binds
        #   the pushed gauges to its proxy target.
        self.decode_engine = decode_engine
        self.push_url = push_url
        self.push_interval_s = float(push_interval_s)
        self._pusher = None
        # request-scoped span push (observability.distributed): a
        # bounded tracer sink drained into each heartbeat push, so the
        # aggregator's TraceStore can stitch this host's handler /
        # batcher / decode spans into per-request waterfalls. Built in
        # start() only when push_url is set; DL4J_TPU_TRACE=0 and
        # DL4J_TPU_TRACE_SAMPLE throttle it at the tracer.
        self._span_push = None
        # SLO engine (observability.slo): declared objectives evaluated
        # over this host's own ServingStats — gauge families on the
        # registry (scrape + federation push for free), and the
        # attainment summary stamped onto the drain RunReport by stop().
        from deeplearning4j_tpu.observability import slo as _slo
        if slos is None:
            slos = _slo.default_serving_slos(p99_bound_ms=float(
                os.environ.get("DL4J_TPU_SLO_P99_MS", "500")))
        self.slo_engine = _slo.SLOEngine(slos) if slos else None
        self._slo_collector = None
        # Live reload (SERVING.md §Live reload): the published weight
        # version currently serving (0 = boot weights, never hot-swapped)
        # and the swap counter — both pushed to the federation plane so
        # a router's canary gates can see WHICH version a host runs.
        self.model_version = 0
        self.swaps_total = 0
        #: strong ref to the hot-swapped (params, state) trees: the
        #: version-bound forward closures alias these on the device
        self._live_weights = None
        self._swap_lock = threading.Lock()

    @property
    def _batcher(self):
        """Replica 0's batcher — the seed single-batcher surface
        (tests patch ``server._batcher._forward``); routing and
        admission live on ``self._fleet``."""
        return self._fleet.replicas[0].batcher

    @property
    def fleet(self) -> ReplicaSet:
        return self._fleet

    # ------------------------------------------------------------ device side
    def _build_serving_net(self, compute_dtype):
        """A shadow net over the same configuration with only the
        policy's compute dtype replaced: structure-only init (no second
        parameter set is ever materialized — ``_device_forward`` aliases
        the primary net's live params/state each call, so a net that is
        still training serves its freshest weights)."""
        import dataclasses as _dc
        gc = self.net.conf.global_conf
        # dataclasses.replace re-runs DtypePolicy validation, so an
        # unknown dtype string fails here, at server build time
        gc2 = _dc.replace(gc, dtype=_dc.replace(
            gc.dtype, compute_dtype=compute_dtype))
        conf2 = _dc.replace(self.net.conf, global_conf=gc2)
        shadow = type(self.net)(conf2)
        shadow.init(structure_only=True)
        return shadow

    @property
    def serving_compute_dtype(self) -> str:
        """The dtype the serving forward actually computes in (the
        ``compute_dtype`` label on serving metrics)."""
        if self.compute_dtype is not None:
            return self.compute_dtype
        return self.net.conf.global_conf.dtype.compute_dtype

    def _device_forward(self, feats):
        """Model adapter run only on the batcher's device thread."""
        net = self.net
        if self._serving_net is not None:
            self._serving_net.params = self.net.params
            self._serving_net.state = self.net.state
            net = self._serving_net
        if self._is_graph:
            return net.output(*feats)
        return net.output(feats[0])

    # ----------------------------------------------------------- live reload
    def _versioned_forward(self, params, state):
        """A forward closure bound to PUBLISHED weights. The trick that
        makes a hot swap free: the serving net's jitted apply already
        takes ``(params, state)`` EXPLICITLY (multilayer._get_apply /
        graph.output), so a closure that calls the SAME jitted function
        with different trees reuses every compiled bucket executable —
        0 fresh compiles, and replicas mid-rolling-swap (some on the old
        version, some on the new) share one jit cache. Nothing on the
        live net is mutated, so there is no publication race with
        requests still finishing on the old weights."""
        import jax.numpy as jnp
        net = self._serving_net if self._serving_net is not None else self.net
        if self._is_graph:
            key = ("out", False, False)
            if key not in net._apply_fns:
                # build the graph's jitted output program exactly the
                # way net.output() would (it closes over structure, not
                # params) so swapped and unswapped paths share it
                import jax

                def fn(p, s, inputs, fmasks):
                    acts, _, _, _ = net._walk(p, s, inputs, train=False,
                                              rng=None, fmasks=fmasks)
                    return tuple(acts[o] for o in net.conf.network_outputs)
                net._apply_fns[key] = jax.jit(fn)

            def forward(feats):
                inputs, fmasks = net._prepare_inputs(
                    [jnp.asarray(f) for f in feats], None)
                outs = net._apply_fns[key](params, state, inputs, fmasks)
                return outs[0] if len(outs) == 1 else list(outs)
        else:
            def forward(feats):
                fn = net._get_apply(collect=False, train=False)
                return fn(params, state, jnp.asarray(feats[0]), None, None)
        return forward

    def hot_swap(self, publication=None, *, net=None, version=None):
        """Zero-downtime reload onto a published version: rolling
        ``swap_forward`` over every replica, each one publish-then-drain
        (fleet.py) — in-flight requests finish on the old weights while
        new admissions run the new ones, and at no instant is the
        replica out of routing. Decode sessions are not supported here
        (their KV caches are entangled with the old weights — drain the
        host and boot a new one off the shared compile cache instead;
        the router fails sessions over via bit-identical re-prefill),
        and mesh serving shards params at build time
        (parallel/inference.py), so it swaps by host replacement too.

        ``publication``: a serving.publish.Publication (its checkpoint
        is restored here unless a pre-restored ``net`` is passed). The
        publication's fingerprint must match the serving net's — same
        param pytree structure is what guarantees the jit-cache reuse.
        Returns a receipt dict: version, replicas swapped, wall time,
        and the XLA compile delta across the swap itself (0 on a warmed
        server — the budget-gated invariant)."""
        if self.mesh is not None:
            raise ValueError(
                "hot_swap is the single-host replica path; mesh serving "
                "shards params at build time — drain this host and boot "
                "a replacement off the shared compile cache instead")
        if self.decode_engine is not None:
            raise ValueError(
                "hot_swap cannot re-weight live decode sessions (KV "
                "caches hold old-weight state) — drain the host; the "
                "router re-prefills sessions onto survivors "
                "bit-identically")
        from deeplearning4j_tpu.compilecache.manifest import model_fingerprint
        from deeplearning4j_tpu.serving import publish as _publish
        with self._swap_lock:
            if publication is not None:
                if net is None:
                    net = _publish.load_net(publication.path)
                if version is None:
                    version = publication.version
                expect = publication.fingerprint
            else:
                if net is None:
                    raise ValueError("hot_swap needs a publication or a "
                                     "pre-restored net")
                expect = model_fingerprint(net)
            serving_fp = model_fingerprint(self.net)
            if expect is not None and expect != serving_fp:
                raise ValueError(
                    f"published fingerprint {expect} does not match the "
                    f"serving net's {serving_fp} — a hot swap can only "
                    "bind weights with the identical param structure "
                    "(different architecture ⇒ boot a new host)")
            # Checkpoint restore commits leaves to an explicit device
            # placement; the live net's params are uncommitted. jit keys
            # on that distinction, so feeding restored leaves straight in
            # retraces once per swap. Round-trip through host memory to
            # shed the committed placement and hit the existing cache.
            import jax
            import jax.numpy as jnp

            def _uncommit(tree):
                return jax.tree_util.tree_map(
                    lambda a: jnp.asarray(np.asarray(a)), tree)
            params = _uncommit(net.params)
            state = _uncommit(net.state) if net.state else {}
            forward = self._versioned_forward(params, state)
            compile0 = _obs_metrics.compile_snapshot()
            t0 = time.perf_counter()
            swapped = 0
            for r in list(self._fleet.replicas):
                if r.status == "dead":
                    continue  # an evicted slot keeps its slot semantics
                self._fleet.swap_forward(r.index, forward)
                swapped += 1
            self._live_weights = (params, state)
            self.model_version = int(version) if version is not None else \
                self.model_version + 1
            self.swaps_total += 1
            delta = _obs_metrics.compile_delta(compile0)
            return {"version": self.model_version,
                    "fingerprint": serving_fp,
                    "replicas_swapped": swapped,
                    "swap_s": round(time.perf_counter() - t0, 6),
                    "fresh_compiles": delta["count"]}

    def _infer_row_shapes(self):
        """Per-input row shapes (no batch dim) for warm-up, when they can
        be derived from the configuration; None disables warm-up."""
        if self.input_shapes is not None:
            return [tuple(s) for s in self.input_shapes]

        def from_itype(it):
            if it is None:
                return None
            if it.kind in ("feed_forward", "convolutional_flat"):
                return (it.size,)
            if it.kind == "convolutional":
                return (it.height, it.width, it.channels)
            if it.kind == "recurrent" and it.timesteps:
                return (it.timesteps, it.size)
            return None

        def from_conf(lc):
            from deeplearning4j_tpu.nn.conf.layers import (
                FeedForwardLayerConfig)
            from deeplearning4j_tpu.nn.conf.layers_recurrent import (
                BaseRecurrentConfig)
            if (isinstance(lc, FeedForwardLayerConfig)
                    and not isinstance(lc, BaseRecurrentConfig)
                    and getattr(lc, "n_in", None)):
                return (lc.n_in,)
            return None

        if self._is_graph:
            its = getattr(self.net.conf, "input_types", None)
            if its:
                shapes = [from_itype(it) for it in its]
                return None if any(s is None for s in shapes) else shapes
            shapes = []
            for name in self.net.conf.network_inputs:
                s = None
                for v, ins in self.net.conf.vertex_inputs.items():
                    if name in ins:
                        s = from_conf(self.net._resolved_confs.get(v))
                        if s is not None:
                            break
                if s is None:
                    return None
                shapes.append(s)
            return shapes
        s = from_itype(getattr(self.net.conf, "input_type", None))
        if s is None and getattr(self.net.conf, "layers", None):
            s = from_conf(self.net.conf.layers[0])
        return None if s is None else [s]

    # ------------------------------------------------------------ inference
    def predict(self, features, trace_id=None, klass=None, tenant=None,
                deadline_ms=None):
        """Enqueue the request into the micro-batcher and wait for the
        scattered result rows. Requests larger than ``max_batch`` are
        split into ``max_batch`` chunks so they reuse the already-compiled
        full-bucket program instead of compiling a fresh XLA executable of
        arbitrary shape. ``features``: one array (sequential net) or list
        of arrays (graph). ``trace_id`` propagates onto the batcher span
        attrs (the HTTP handler passes the client's ``X-DL4J-Trace-Id``);
        ``klass`` / ``tenant`` / ``deadline_ms`` are the scheduling
        headers (X-DL4J-Priority / -Tenant / -Deadline-Ms) threaded into
        fleet admission the same way. Raises QueueFullError (or its
        ShedError subclass naming the shed class) when admission control
        rejects (mapped to HTTP 503)."""
        t0 = time.perf_counter()
        many = isinstance(features, (list, tuple))
        if many and not self._is_graph and len(features) != 1:
            raise ValueError(
                "this model takes ONE features array — use the "
                '{"features": [...]} payload (the "inputs" list form is '
                "for multi-input graphs)")
        feats = [np.asarray(f, np.float32)
                 for f in (features if many else [features])]
        n = feats[0].shape[0]
        if any(f.shape[0] != n for f in feats):
            raise ValueError("all inputs must have the same number of rows")
        self._fleet.start()  # idempotent; lazy for direct predict() use
        futures = [self._fleet.submit(
                       [f[i:i + self.max_batch] for f in feats],
                       trace_id=trace_id, klass=klass, tenant=tenant,
                       deadline_ms=deadline_ms)
                   for i in range(0, max(n, 1), self.max_batch)]
        # one deadline for the whole request, not per chunk: the budget
        # left after chunk k is what chunk k+1 may spend
        deadline = t0 + self.request_timeout_s
        chunks = []
        for f in futures:
            try:
                chunks.append(f.result(
                    timeout=max(0.0, deadline - time.perf_counter())))
            except _FutureTimeout:
                self.stats.record_timeout()
                raise DeadlineExceededError(
                    f"request exceeded {self.request_timeout_s:g}s "
                    "deadline") from None
        if isinstance(chunks[0], list):
            out = [np.concatenate([c[k] for c in chunks])
                   if len(chunks) > 1 else chunks[0][k]
                   for k in range(len(chunks[0]))]
        else:
            out = (np.concatenate(chunks) if len(chunks) > 1 else chunks[0])
        # serving NaN sentinel: count reply rows carrying non-finite
        # values. The reply is still served (a canary's whole point is
        # measuring the bad version on real traffic) — the counter rides
        # the federation push, where the router's promotion gates kill
        # the version before it leaves its traffic fraction.
        nan_rows = 0
        for a in (out if isinstance(out, list) else [out]):
            a = np.asarray(a)
            flat = a.reshape(a.shape[0], -1) if a.ndim > 1 \
                else a.reshape(-1, 1)
            nan_rows += int((~np.isfinite(flat).all(axis=1)).sum())
        if nan_rows:
            self.stats.record_nan_rows(nan_rows)
        self.stats.record_request(n, time.perf_counter() - t0)
        return out

    # -------------------------------------------------------------- server
    def _validate_aot_manifest(self, row_shapes):
        """Check the precompile manifest (explicit path/dict, or
        auto-located in the cache dir) against THIS boot's serving
        config. A mismatch means the cached executables were built for
        a different program: warn — loudly, a boot that believes it is
        warm but compiles fresh is a silent perf regression — and fall
        back to lazy compile. Never raises; sets ``aot_manifest_ok``."""
        import warnings

        from deeplearning4j_tpu.compilecache import manifest as _man
        from deeplearning4j_tpu.serving.batcher import bucket_ladder
        src = self.aot_manifest
        if src is None and self.compile_cache_dir is not None:
            auto = os.path.join(self.compile_cache_dir, _man.MANIFEST_NAME)
            if os.path.exists(auto):
                src = auto
        if src is None:
            return
        try:
            man = src if isinstance(src, dict) else _man.load(src)
            mb = self._batcher
            mismatches = _man.validate_serving(
                man, self.net, row_shapes=row_shapes or (),
                ladder=bucket_ladder(mb.min_batch, mb.max_batch),
                max_batch=mb.max_batch, min_batch=mb.min_batch,
                compute_dtype=self.serving_compute_dtype, mesh=self.mesh)
        except Exception as e:
            mismatches = [f"unreadable manifest: {type(e).__name__}: {e}"]
        self.aot_manifest_ok = not mismatches
        if mismatches:
            warnings.warn(
                "AOT precompile manifest does not match this serving "
                "config — falling back to lazy compile (this boot pays "
                "fresh XLA compiles): " + "; ".join(mismatches),
                RuntimeWarning, stacklevel=3)

    def start(self):
        server = self

        # compile baseline taken BEFORE warm-up, so the serving RunReport
        # charges the warm-up ladder's compiles (and cache hits/misses)
        # to this run — that delta is exactly what a warm cache zeroes
        compile0 = _obs_metrics.compile_snapshot()
        stages0 = _obs_metrics.stage_snapshot()
        if self.warmup:
            shapes = self._infer_row_shapes()
            self._validate_aot_manifest(shapes)
            if shapes is not None:
                t_warm = time.perf_counter()
                try:
                    # hoisted: one ladder per distinct forward, however
                    # many replicas share it (fleet.warm)
                    self._fleet.warm(shapes)
                    self.warmup_s = round(time.perf_counter() - t_warm, 6)
                except Exception:
                    # warm-up is an optimization: a shape-inference miss
                    # must never block serving (first requests compile
                    # lazily, exactly as the seed server did)
                    self.shapes_seen.clear()
        self._fleet.start()

        class Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 keep-alive: closed-loop clients reuse their
            # connection instead of paying a TCP handshake per request
            # (every reply carries Content-Length, so this is safe).
            # Nagle off, or the two-segment request/reply pattern hits
            # the 40 ms delayed-ACK stall on every round trip.
            protocol_version = "HTTP/1.1"
            disable_nagle_algorithm = True

            def log_message(self, *args):
                pass

            def _json(self, obj, code=200, headers=()):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in headers:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _text(self, text, code=200,
                      content_type=_obs_metrics.PROMETHEUS_CONTENT_TYPE):
                body = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                if self.path.startswith("/healthz"):
                    rows = server._fleet.describe()
                    if not server._fleet.healthy:
                        # every device thread dead means every /predict
                        # would hang or 503 — report down so the load
                        # balancer stops routing here
                        self._json({"status": "unhealthy",
                                    "reason": "batcher device thread dead",
                                    "replicas": rows}, 503)
                        return
                    n_live = sum(1 for r in rows if r["status"] == "live")
                    # some replicas down but traffic still flows:
                    # degraded, not down — the router keeps the node but
                    # the scoreboard shows the hole
                    self._json({"status": ("ok" if n_live == len(rows)
                                           else "degraded"),
                                "params": int(server.net.num_params()),
                                "graph": server._is_graph,
                                "model_version": server.model_version,
                                "replicas": rows})
                elif self.path.startswith("/metrics"):
                    if "format=snapshot" in self.path:
                        # federation wire form: full-fidelity families +
                        # identity + health, for an aggregator's scrape
                        from deeplearning4j_tpu.observability import \
                            distributed as _dist
                        self._json(_dist.export_snapshot(
                            health={"batcher_healthy":
                                    server._fleet.healthy,
                                    "replicas":
                                    server._fleet.describe()}))
                    elif _obs_metrics.wants_prometheus(
                            self.headers.get("Accept", ""), self.path):
                        # the full unified registry (serving + resilience
                        # + compile + device-memory series), not just the
                        # serving slice — one scrape sees the process
                        self._text(_obs_metrics.get_registry()
                                   .render_prometheus())
                    else:
                        self._json(server.metrics())
                else:
                    self._json({"error": "not found"}, 404)

            def _decode_op(self, payload, trace_id=None):
                """Host half of the cross-host decode protocol: the
                request always carries the session's full token history
                (``ids``), so a ``step`` for a sid this host has never
                seen — a router failover after another host died — is
                answered by re-prefilling from that history first. The
                re-prefill is bit-identical to the steps it replaces
                (serving/decode.py), so the reply is too. ``trace_id``
                threads through to the engine's prefill/step/verify
                spans and batcher tickets. An unknown sid with no
                history raises UnknownSessionError — HTTP 404, distinct
                from the 400 a malformed op earns."""
                eng = server.decode_engine
                op = payload.get("op")
                sid = payload["sid"]
                if op == "prefill":
                    logits = eng.prefill(sid, payload["ids"],
                                         trace_id=trace_id)
                    return {"logits": np.asarray(logits).tolist()}
                if op == "step":
                    recovered = False
                    if sid not in eng.sessions:
                        ids = payload.get("ids") or ()
                        if not ids:
                            raise UnknownSessionError(
                                f"unknown decode session '{sid}' and no "
                                "ids history to recover from")
                        eng.prefill(sid, ids, trace_id=trace_id)
                        recovered = True
                    logits = eng.step(sid, int(payload["token"]),
                                      trace_id=trace_id)
                    return {"logits": np.asarray(logits).tolist(),
                            "recovered": recovered}
                if op == "generate":
                    # multi-token op: the host runs the whole greedy
                    # loop (speculative rounds when the engine has a
                    # draft), so speculation's launch savings survive
                    # the wire — a per-step protocol would serialize
                    # every token through a round trip
                    ids = payload.get("ids") or ()
                    if not ids:
                        raise KeyError(
                            f"decode generate for '{sid}' needs ids")
                    toks = eng.generate(sid, [int(i) for i in ids],
                                        int(payload.get("n_tokens", 0)),
                                        trace_id=trace_id)
                    return {"tokens": [int(t) for t in toks],
                            "speculative": bool(eng.spec_k)}
                if op == "close":
                    return {"closed": eng.close_session(sid)}
                raise ValueError(f"unknown decode op {op!r}")

            def do_POST(self):  # noqa: N802
                is_decode = (self.path.startswith("/decode")
                             and server.decode_engine is not None)
                if not self.path.startswith("/predict") and not is_decode:
                    self._json({"error": "not found"}, 404)
                    return
                # trace-context propagation: accept the client's id (or
                # mint one) so batcher spans carry it, and echo it back
                # so the client can stitch both timelines together
                from deeplearning4j_tpu.observability import \
                    distributed as _dist
                from deeplearning4j_tpu.scheduling import core as _sched
                trace_id = (self.headers.get(_dist.TRACE_HEADER)
                            or _dist.new_trace_id())
                echo = ((_dist.TRACE_HEADER, trace_id),)
                # scheduling-context propagation, same contract: the
                # tenant/priority/deadline headers thread into fleet
                # admission and echo back normalized
                sched = _sched.parse_sched_headers(self.headers)
                echo += ((_sched.PRIORITY_HEADER, sched["klass"]),)
                if sched["tenant"]:
                    echo += ((_sched.TENANT_HEADER, sched["tenant"]),)
                if sched["deadline_ms"] is not None:
                    echo += ((_sched.DEADLINE_HEADER,
                              f"{sched['deadline_ms']:g}"),)
                # one handler span per request, trace-tagged and
                # carrying server_url — the span the aggregator's
                # TraceStore centers inside the router's send/recv hop
                # window to rebase this host's clock (error paths
                # included: a failed request still explains its time)
                with _obs_trace.get_tracer().span(
                        "decode_op" if is_decode else "predict_handler",
                        trace_id=trace_id, server_url=server.url):
                    self._handle_post(is_decode, trace_id, echo, sched)

            def _handle_post(self, is_decode, trace_id, echo, sched):
                from deeplearning4j_tpu.scheduling.core import (
                    SHED_CLASS_HEADER, ShedError)
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n).decode())
                    if is_decode:
                        self._json(self._decode_op(payload,
                                                   trace_id=trace_id),
                                   headers=echo)
                        return
                    if "inputs" in payload:
                        out = server.predict([np.asarray(a) for a in
                                              payload["inputs"]],
                                             trace_id=trace_id, **sched)
                    else:
                        out = server.predict(np.asarray(payload["features"]),
                                             trace_id=trace_id, **sched)
                    if isinstance(out, list):
                        preds = [np.asarray(o).tolist() for o in out]
                    else:
                        preds = np.asarray(out).tolist()
                    self._json({"predictions": preds}, headers=echo)
                except QueueFullError as e:
                    # backpressure: shed load instead of growing the
                    # queue. Retry-After is DERIVED: current backlog over
                    # the observed drain rate, clamped to [0.05s, 5s] —
                    # a fast-draining fleet calls clients back sooner.
                    # X-DL4J-Shed-Class names WHICH class was shed (the
                    # ShedError knows; a legacy full-queue reject sheds
                    # the request's own class) so load tests can verify
                    # batch sheds before interactive.
                    shed_k = e.klass if isinstance(e, ShedError) \
                        else sched["klass"]
                    if not isinstance(e, ShedError) \
                            and server.scheduler is not None:
                        server.scheduler.record_shed(shed_k)
                    self._json({"error": f"overloaded: {e}"}, 503,
                               headers=(("Retry-After",
                                         f"{server.stats.retry_after_s():g}"
                                         ),
                                        (SHED_CLASS_HEADER, shed_k)) + echo)
                except BatcherDeadError as e:
                    # dead device thread: same 503 the health check gives
                    self._json({"error": f"unhealthy: {e}"}, 503,
                               headers=echo)
                except DeadlineExceededError as e:
                    self._json({"error": str(e)}, 504, headers=echo)
                except UnknownSessionError as e:
                    # routing miss, not a malformed request: the router
                    # recovers by re-prefill elsewhere, so it is not
                    # counted against this host's error budget
                    self._json({"error": str(e)}, 404, headers=echo)
                except Exception as e:  # surface as a 400, keep serving
                    server.stats.record_error()
                    self._json({"error": f"{type(e).__name__}: {e}"}, 400,
                               headers=echo)

        self._httpd = _ServingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        _obs_metrics.install_runtime_metrics()
        self.stats.attach_to_registry(
            labels={"server": f"{self.host}:{self.port}",
                    "compute_dtype": self.serving_compute_dtype},
            shapes_fn=lambda: self.shapes_seen)
        self._attach_fleet_collector()
        self._attach_decode_collector()
        self._attach_slo_collector()
        self._attach_sched_collector()
        self._ledger = _goodput.start_run("serving", net=self.net)
        self._ledger.rebase_compile(compile0, stages0)
        if self.warmup_s is not None:
            self._ledger.annotate(warmup_s=self.warmup_s)
        from deeplearning4j_tpu.observability import distributed as _dist
        _dist.stamp_run_marker("serving")
        import threading
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        if self.push_url:
            # worker-fleet -> router federation heartbeat: retry is ON
            # (attempts=3, jittered backoff) so a router restart costs
            # one delayed push, not this host's scoreboard row.
            # Trace-tagged spans ride the same pushes (SpanPushBuffer
            # drains into the snapshot's "spans" key) so the router can
            # stitch per-request waterfalls without a second wire.
            self._span_push = _dist.SpanPushBuffer().install()
            self._pusher = _dist.HeartbeatPusher(
                self.push_url, self.push_interval_s,
                health_fn=self._push_health,
                spans_fn=self._span_push.payload).start()
        return self

    def _push_health(self) -> dict:
        """The health payload each federation push carries: readiness
        plus ``server_url`` — the key a FrontDoorRouter joins pushed
        gauges to its proxy target by."""
        snap = self.stats.snapshot(self.shapes_seen)
        health = {"batcher_healthy": self._fleet.healthy,
                  "server_url": self.url,
                  "model_version": self.model_version,
                  "replicas": self._fleet.describe(),
                  # the canary-gate slice: the few counters a router's
                  # promotion gates difference against their baseline
                  # (serving/router.py start_canary/evaluate_canary)
                  "serving": {
                      "requests_total": snap["requests_total"],
                      "errors_total": snap["errors_total"],
                      "timeouts_total": snap["timeouts_total"],
                      "nan_rows_total": snap["nan_rows_total"],
                      "latency_p99_ms": snap["latency_ms"]["p99"],
                  }}
        if self.decode_engine is not None:
            health["decode"] = self.decode_engine.describe()
        return health

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def metrics(self) -> dict:
        """ServingStats snapshot (same payload as ``GET /metrics``),
        plus the per-replica health rows and eviction-requeue count."""
        snap = self.stats.snapshot(self.shapes_seen)
        snap["replicas"] = self._fleet.describe()
        snap["requeued_total"] = self._fleet.requeued
        snap["model_version"] = self.model_version
        snap["weight_swaps_total"] = self.swaps_total
        if self.scheduler is not None:
            snap["sched"] = self.scheduler.snapshot()
        if self.decode_engine is not None:
            snap["decode"] = self.decode_engine.describe()
        return snap

    def _attach_fleet_collector(self):
        """Per-replica gauges on the unified registry. Each replica gets
        its own ``instance`` label, ``<identity.tag>/r<k>`` — the same
        key scheme the federation aggregator files instances under, so a
        merged fleet view distinguishes replicas without a new label
        vocabulary. Distinct family names (``dl4j_serving_replica_*``)
        keep the exposition free of duplicate-family clashes with the
        fleet-total serving series."""
        from deeplearning4j_tpu.observability import distributed as _dist
        from deeplearning4j_tpu.observability.metrics import MetricFamily
        score = {"live": 1.0, "draining": 0.5, "dead": 0.0}
        addr = f"{self.host}:{self.port}"

        def _collect():
            tag = _dist.get_identity().tag
            depth = MetricFamily(
                "dl4j_serving_replica_queue_depth", "gauge",
                "Tickets pending per fleet replica (the routing signal)")
            up = MetricFamily(
                "dl4j_serving_replica_up", "gauge",
                "Replica status: 1 live, 0.5 draining, 0 dead")
            for row in self._fleet.describe():
                labels = {"instance": f"{tag}/r{row['replica']}",
                          "server": addr}
                depth.add(row["queue_depth"], labels)
                up.add(score.get(row["status"], 0.0),
                       {**labels, "status": row["status"]})
            requeued = MetricFamily(
                "dl4j_serving_requeued_total", "counter",
                "Tickets resubmitted onto survivors after an eviction")
            requeued.add(self._fleet.requeued, {"server": addr})
            version = MetricFamily(
                "dl4j_serving_model_version", "gauge",
                "Published weight version currently serving (0 = boot "
                "weights, never hot-swapped)")
            version.add(self.model_version, {"server": addr})
            swaps = MetricFamily(
                "dl4j_serving_weight_swaps_total", "counter",
                "Completed zero-downtime weight hot swaps")
            swaps.add(self.swaps_total, {"server": addr})
            return [depth, up, requeued, version, swaps]

        reg = _obs_metrics.get_registry()
        reg.register_collector(_collect)
        self._fleet_collector = (reg, _collect)

    def _attach_decode_collector(self):
        """Decode/KV-pool gauges (shared pages, dedup ratio, chunked
        prefills) on the unified registry — present only when a decode
        engine rides this server. ``export_snapshot`` reads the same
        registry, so these series reach the federation wire form with
        no extra plumbing."""
        if self.decode_engine is None:
            return
        from deeplearning4j_tpu.serving.metrics import decode_metric_families
        addr = f"{self.host}:{self.port}"

        def _collect():
            return decode_metric_families(self.decode_engine.describe(),
                                          {"server": addr})

        reg = _obs_metrics.get_registry()
        reg.register_collector(_collect)
        self._decode_collector = (reg, _collect)

    def _attach_slo_collector(self):
        """SLO gauge families on the unified registry. The collector
        ingests a fresh stats snapshot per render, so every scrape (and
        every federation push, which reads the same registry) advances
        the sliding windows — scrape-driven evaluation, the standard
        Prometheus shape."""
        if self.slo_engine is None:
            return

        def _collect():
            self.slo_engine.ingest(self.stats.snapshot(self.shapes_seen))
            return self.slo_engine.families()

        reg = _obs_metrics.get_registry()
        reg.register_collector(_collect)
        self._slo_collector = (reg, _collect)

    def _attach_sched_collector(self):
        """``dl4j_sched_*`` families (per-class admitted/shed counters,
        per-tenant quota-token gauges) on the unified registry — the
        satellite contract that lets a load test watch batch shed while
        interactive is admitted. Federation pushes read the same
        registry, so the router sees these series for free."""
        if self.scheduler is None:
            return
        addr = f"{self.host}:{self.port}"

        def _collect():
            return self.scheduler.metric_families({"server": addr})

        reg = _obs_metrics.get_registry()
        reg.register_collector(_collect)
        self._sched_collector = (reg, _collect)

    def stop(self):
        """Stop accepting, then drain: every accepted ticket completes
        before the device thread exits. Closes the serving goodput
        ledger — ``self.run_report`` holds the RunReport afterwards."""
        if self._pusher is not None:
            self._pusher.stop()
            self._pusher = None
        if self._span_push is not None:
            self._span_push.remove()
            self._span_push = None
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self.decode_engine is not None:
            self.decode_engine.stop()
        self._fleet.stop()
        self.stats.detach_from_registry()
        if self._fleet_collector is not None:
            reg, collect = self._fleet_collector
            reg.unregister_collector(collect)
            self._fleet_collector = None
        if self._decode_collector is not None:
            reg, collect = self._decode_collector
            reg.unregister_collector(collect)
            self._decode_collector = None
        if self._slo_collector is not None:
            reg, collect = self._slo_collector
            reg.unregister_collector(collect)
            self._slo_collector = None
        if self._sched_collector is not None:
            reg, collect = self._sched_collector
            reg.unregister_collector(collect)
            self._sched_collector = None
        ledger = getattr(self, "_ledger", None)
        if ledger is not None and self.slo_engine is not None:
            # final ingest + stamp: the drain report carries the run's
            # SLO attainment next to its goodput numbers
            self.slo_engine.ingest(self.stats.snapshot(self.shapes_seen))
            ledger.annotate(slo=self.slo_engine.report())
        if ledger is not None and self.stats.first_reply_unix is not None:
            # time-to-first-reply from PROCESS start (kernel starttime):
            # imports + model build + compiles + warm-up, the whole cold
            # bill — not just the slice since this server object existed
            ledger.annotate(cold_start_s=round(
                self.stats.first_reply_unix
                - _obs_metrics.process_start_unix(), 6))
        report = _goodput.end_run(ledger)
        if report is not None:  # stop() is idempotent; keep the first
            self.run_report = report


def serve(net, host: str = "127.0.0.1", port: int = 9500,
          max_batch: int = 1024, batch_window_ms: float = 2.0,
          max_queue: int = 1024, warmup: bool = True,
          input_shapes=None, request_timeout_s: float = 300.0,
          compute_dtype=None, replicas: int = 1, mesh=None,
          model_axis: str = "model", data_axis=None,
          tp_rules=None, compile_cache_dir=None, aot_manifest=None,
          tuning_report=None, decode_engine=None, push_url=None,
          push_interval_s: float = 2.0, slos=None,
          scheduler=None) -> ModelServer:
    """One-call serving entry point: ``serve(net).url`` is live."""
    return ModelServer(net, host, port, max_batch,
                       batch_window_ms=batch_window_ms, max_queue=max_queue,
                       warmup=warmup, input_shapes=input_shapes,
                       request_timeout_s=request_timeout_s,
                       compute_dtype=compute_dtype, replicas=replicas,
                       mesh=mesh, model_axis=model_axis,
                       data_axis=data_axis, tp_rules=tp_rules,
                       compile_cache_dir=compile_cache_dir,
                       aot_manifest=aot_manifest,
                       tuning_report=tuning_report,
                       decode_engine=decode_engine, push_url=push_url,
                       push_interval_s=push_interval_s,
                       slos=slos, scheduler=scheduler).start()
