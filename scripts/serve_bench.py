"""Closed-loop serving load generator: before/after for the
continuous-batching inference runtime.

**CPU control-flow drill (ROADMAP D3).** This script defaults
``JAX_PLATFORMS`` to ``cpu`` and starts several device-owning child
processes, which one TPU chip cannot host (a chip belongs to one
process). Its counts hold on any backend; its timings are CPU
wall-clock and say nothing about a TPU. The chip check is
``chip_smoke.py``.

Measures end-to-end HTTP rows/sec and latency percentiles for the MNIST
MLP at client concurrency 1 / 8 / 64, against BOTH server designs:

- ``serialized`` — the seed design, reimplemented inline as the
  baseline: one forward per HTTP request under a global lock (the
  accelerator idles between per-request dispatches).
- ``coalesced``  — the continuous micro-batching ModelServer
  (serving/batcher.py): handler threads enqueue, one device thread
  coalesces pending requests into padded power-of-two bucket forwards.

Every client is closed-loop (fires its next request only after the
previous reply) over a persistent HTTP/1.1 connection, and every reply
is checked BIT-IDENTICAL against the sequential ``net.output()``
reference rows — a speedup that changed the numbers would not count.

Run: ``python scripts/serve_bench.py`` (CPU is fine; add ``--quick``
for the fast variant bench.py embeds in its ``extra`` dict).
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# --------------------------------------------------------------- baseline
class SerializedServer:
    """The seed lock-serialized server, kept verbatim as the bench
    baseline: pad each request to its own power-of-two bucket, run ONE
    forward per request under a global lock."""

    def __init__(self, net, max_batch: int = 1024):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from deeplearning4j_tpu.serving.batcher import next_bucket

        self.net = net
        self.max_batch = max_batch
        self._lock = threading.Lock()
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            disable_nagle_algorithm = True

            def log_message(self, *args):
                pass

            def do_POST(self):  # noqa: N802
                n = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(n).decode())
                x = np.asarray(payload["features"], np.float32)
                rows = x.shape[0]
                # same min-bucket floor as the coalescing server so both
                # designs produce identical rows and the comparison
                # isolates the dispatch architecture, not the gemv/gemm
                # code-path split
                bucket = next_bucket(rows, outer.max_batch, 2)
                if bucket != rows:
                    x = np.pad(x, [(0, bucket - rows), (0, 0)])
                with outer._lock:
                    out = np.asarray(outer.net.output(x))[:rows]
                body = json.dumps({"predictions": out.tolist()}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        class Server(ThreadingHTTPServer):
            request_queue_size = 128  # survive a 64-client connect burst

        self._httpd = Server(("127.0.0.1", 0), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


# ------------------------------------------------------------ load client
def run_load(port: int, x: np.ndarray, reference: np.ndarray,
             concurrency: int, requests_per_client: int,
             capture_trace: bool = False) -> dict:
    """``concurrency`` closed-loop clients, each firing
    ``requests_per_client`` single-row /predict posts over one
    persistent connection. Returns rows/sec + latency percentiles and a
    row-exactness verdict. ``capture_trace`` also records each request's
    arrival offset (seconds since the start gate) so the run can be
    replayed offline by the schedule autotuner
    (compilecache.autotune)."""
    from deeplearning4j_tpu.observability.distributed import (TRACE_HEADER,
                                                              new_trace_id)
    lats: list[float] = []
    lock = threading.Lock()
    errors: list[str] = []
    mismatches = [0]
    # trace-context propagation receipts: ids sent, ids echoed back
    trace_ids = {"sent": 0, "echoed": 0}
    arrivals: list = []   # (perf_counter at send, rows) when capturing
    start_gate = threading.Event()

    def client(tid: int):
        import socket

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        my_lats = []
        my_arr = []
        my_sent = my_echoed = 0
        try:
            conn.connect()
            # Nagle off: header and body go out as separate sends, and
            # Nagle + delayed ACK turns that into a 40 ms stall per post
            conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            start_gate.wait()
            for r in range(requests_per_client):
                i = (tid * requests_per_client + r) % x.shape[0]
                body = json.dumps({"features": x[i:i + 1].tolist()})
                # every request carries its own trace id; a conforming
                # server echoes it and stamps it onto its batcher spans
                trace_id = new_trace_id()
                my_sent += 1
                t0 = time.perf_counter()
                if capture_trace:
                    my_arr.append((t0, 1))
                conn.request("POST", "/predict", body,
                             {"Content-Type": "application/json",
                              TRACE_HEADER: trace_id})
                resp = conn.getresponse()
                data = resp.read()
                my_lats.append(time.perf_counter() - t0)
                if resp.getheader(TRACE_HEADER) == trace_id:
                    my_echoed += 1
                if resp.status != 200:
                    with lock:
                        errors.append(f"HTTP {resp.status}: {data[:120]!r}")
                    return
                got = np.asarray(json.loads(data)["predictions"])
                if not np.array_equal(got[0], reference[i]):
                    with lock:
                        mismatches[0] += 1
        except Exception as e:
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            conn.close()
            with lock:
                lats.extend(my_lats)
                arrivals.extend(my_arr)
                trace_ids["sent"] += my_sent
                trace_ids["echoed"] += my_echoed

    threads = [threading.Thread(target=client, args=(t,), daemon=True)
               for t in range(concurrency)]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    start_gate.set()
    for t in threads:
        t.join(timeout=600.0)
    wall = time.perf_counter() - t0
    if errors:
        return {"error": errors[0], "concurrency": concurrency}
    total = concurrency * requests_per_client
    s = sorted(lats)

    def pct(q):
        return round(1000.0 * s[min(len(s) - 1, int(round(q * (len(s) - 1))))],
                     3)

    if capture_trace:
        trace = {"concurrency": concurrency,
                 "arrivals": sorted(
                     [round(t - t0, 6), r] for t, r in arrivals)}
    return {
        **({"trace": trace} if capture_trace else {}),
        "concurrency": concurrency,
        "requests": total,
        "rows_per_sec": round(total / wall, 1),
        "wall_s": round(wall, 3),
        "p50_ms": pct(0.50), "p95_ms": pct(0.95), "p99_ms": pct(0.99),
        "bit_identical": mismatches[0] == 0,
        "mismatched_rows": mismatches[0],
        # echo rate is 1.0 against ModelServer; the serialized baseline
        # predates trace propagation and reports 0.0 honestly
        "trace_ids_sent": trace_ids["sent"],
        "trace_id_echo_rate": round(
            trace_ids["echoed"] / trace_ids["sent"], 4)
        if trace_ids["sent"] else None,
    }


# ---------------------------------------------------------------- harness
def _serving_mlp(hidden: int = 4096, depth: int = 3):
    """The bench model: a 64-in MLP with ``depth`` x ``hidden`` layers
    (~34M params at the default). Small input dim keeps the JSON wire
    cost off the measurement; the wide hidden stack makes every forward
    weight-streaming-bound, so a single-row forward costs nearly as much
    as a full bucket — exactly the regime where per-request dispatch
    wastes the device and cross-request coalescing multiplies
    throughput (the accelerator-serving shape of the problem, on CPU)."""
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import Dense, Output
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    b = (NeuralNetConfiguration.builder().seed(1).list()
         .layer(Dense(n_in=64, n_out=hidden, activation="relu")))
    for _ in range(depth - 1):
        b = b.layer(Dense(n_in=hidden, n_out=hidden, activation="relu"))
    b = b.layer(Output(n_in=hidden, n_out=10, activation="softmax",
                       loss="mcxent"))
    return MultiLayerNetwork(b.build()).init()


def bench_serving(concurrencies=(1, 8, 64), requests_per_client=25,
                  max_batch: int = 64, batch_window_ms: float = 2.0,
                  hidden: int = 4096, depth: int = 3) -> dict:
    """Run the serialized baseline and the coalescing server over the
    same traffic; returns the full before/after report (the dict
    bench.py embeds under ``extra["serving"]``)."""
    from deeplearning4j_tpu.serving import serve

    net = _serving_mlp(hidden, depth)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 64)).astype(np.float32)
    reference = np.asarray(net.output(x))  # sequential reference rows

    report: dict = {"model": f"serving_mlp 64-{hidden}x{depth}-10 f32 "
                             f"({int(net.num_params()) / 1e6:.1f}M params)",
                    "max_batch": max_batch,
                    "batch_window_ms": batch_window_ms,
                    "platform": _platform(),
                    "serialized": {}, "coalesced": {}}

    base = SerializedServer(net, max_batch=max_batch)
    try:
        for c in concurrencies:
            report["serialized"][f"c{c}"] = run_load(
                base.port, x, reference, c, requests_per_client)
    finally:
        base.stop()

    server = serve(net, port=0, max_batch=max_batch,
                   batch_window_ms=batch_window_ms)
    try:
        for c in concurrencies:
            # capture the arrival trace once, at the highest-concurrency
            # coalesced run (the traffic shape worth autotuning for);
            # run_load returns it inline and it moves to report["trace"]
            res = run_load(server.port, x, reference, c,
                           requests_per_client,
                           capture_trace=(c == max(concurrencies)))
            if "trace" in res:
                report["trace"] = res.pop("trace")
            report["coalesced"][f"c{c}"] = res
        report["metrics"] = server.metrics()
    finally:
        server.stop()
    if server.run_report is not None:
        # the serving goodput ledger closed on drain: device-time share
        # and the bucket ladder's padding waste ride the results file
        report["run_report"] = server.run_report.to_dict()
        # SLO attainment over the bench's own load — the engine's
        # sliding windows closed with the drain, so the --out receipt
        # carries attainment / burn-rate / budget-remaining per SLO
        if report["run_report"].get("slo"):
            report["slo"] = report["run_report"]["slo"]

    for c in concurrencies:
        a = report["serialized"][f"c{c}"].get("rows_per_sec")
        b = report["coalesced"][f"c{c}"].get("rows_per_sec")
        if a and b:
            report[f"speedup_c{c}"] = round(b / a, 2)

    # headline rollup for downstream consumers (perf_probe, budgets):
    # worst-case p99 + best rows/sec across the coalesced runs, plus the
    # batcher's coalesce ratio and padding-waste fraction
    coal = [v for v in report["coalesced"].values() if "p99_ms" in v]
    if coal:
        rr = report.get("run_report") or {}
        report["summary"] = {
            "p50_ms": min(v["p50_ms"] for v in coal),
            "p99_ms": max(v["p99_ms"] for v in coal),
            "rows_per_sec": max(v["rows_per_sec"] for v in coal),
            "coalesce_rows_per_batch":
                report["metrics"].get("coalesce_rows_per_batch"),
            "padding_waste_fraction":
                report["metrics"].get("padding_waste_fraction"),
            "bit_identical": all(v.get("bit_identical") for v in coal),
            # cold-start numbers from the server's own goodput report:
            # process start -> first successful reply, and the warm-up
            # ladder's wall time (check_budgets gates these)
            "cold_start_s": rr.get("cold_start_s"),
            "warmup_s": rr.get("warmup_s"),
            # headline SLO: availability attainment over the bench load
            "slo_availability": (((report.get("slo") or {}).get("slos")
                                  or {}).get("availability")
                                 or {}).get("attainment"),
        }
    return report


def _platform() -> str:
    try:
        import jax
        return jax.devices()[0].platform
    except Exception:
        return "unknown"


# ------------------------------------------------------------ decode bench
def bench_decode(sessions: int = 12, gen_tokens: int = 24,
                 replicas: int = 2, n_pages: int = 40,
                 page_tokens: int = 16, max_batch: int = 16,
                 batch_window_ms: float = 2.0, vocab: int = 32,
                 width: int = 64, n_layers: int = 2, n_heads: int = 4,
                 max_cache_len: int = 128, shared_prefix: int = 32,
                 stagger_s: float = 0.04, net=None,
                 speculative_k: int = 0, draft_net=None) -> dict:
    """Mixed prefill/decode open-arrival load (config ``transformer``,
    the TRANSFORMER_r02 arm): ``sessions`` greedy-decode clients arrive
    STAGGERED (``stagger_s`` apart, open arrival — not a closed-loop
    start gate), so long prompt prefills land while earlier sessions are
    mid-decode: exactly the head-of-line collision chunked prefill
    exists to break. Prompt lengths are heavy-tailed (most short, every
    fourth group 48-64 suffix tokens), every prompt opens with the same
    ``shared_prefix``-token system prompt, and sessions arrive in small
    groups asking the SAME prompt (the millions-of-users shape) — the
    traffic prefix sharing deduplicates.

    Every session's generated token stream is checked against a
    sequential ``rnn_time_step`` reference computed beforehand, and one
    session's logits are checked bit-for-bit — so the published
    inter-token p99 and dedup ratio are for decoding that provably
    chunks, shares, and coalesces without changing a single output (the
    fixed-extent-cache contract, ops/attention.py). The receipt also
    carries the post-warm compile delta: the chunk ladder must add no
    fresh compiles during the timed run.

    ``net=`` substitutes a prebuilt (possibly trained) target;
    ``speculative_k``/``draft_net`` turn on speculative decoding — the
    references stay sequential ``rnn_time_step``, so the bit-identity
    check then covers chunking + sharing + speculation stacked."""
    from deeplearning4j_tpu.observability.metrics import (compile_delta,
                                                          compile_snapshot)
    from deeplearning4j_tpu.serving.decode import DecodeEngine
    from deeplearning4j_tpu.zoo import F32, gpt_mini

    if net is None:
        net = gpt_mini(vocab_size=vocab, width=width, n_layers=n_layers,
                       n_heads=n_heads, max_len=max_cache_len,
                       max_cache_len=max_cache_len, dtype=F32)
    rng = np.random.default_rng(0)
    # shared system prompt + per-group suffix; 3-ish sessions per group
    prefix = [int(t) for t in rng.integers(0, vocab, shared_prefix)]
    n_groups = max(2, sessions // 3)
    suffix_lens = [int(rng.integers(4, 16)) for _ in range(n_groups)]
    for g in range(0, n_groups, 3):
        suffix_lens[g] = int(rng.integers(48, 65))   # the heavy tail
    group_prompts = [
        prefix + [int(t) for t in rng.integers(0, vocab, n)]
        for n in suffix_lens]
    # arrival order starts on a SHORT group so the heavy-tail prompts
    # land while earlier sessions are mid-decode — the head-of-line
    # collision this arm exists to measure
    gid = [(i + 1) % n_groups for i in range(sessions)]
    prompts = [group_prompts[g] for g in gid]

    def oh(ids):
        xx = np.zeros((1, len(ids), vocab), np.float32)
        xx[0, np.arange(len(ids)), ids] = 1.0
        return xx

    def ref_generate(ids):
        net.rnn_clear_previous_state()
        o = np.asarray(net.rnn_time_step(oh(ids)))[0, -1]
        seq = []
        for _ in range(gen_tokens):
            nxt = int(np.argmax(o))
            seq.append(nxt)
            o = np.asarray(net.rnn_time_step(oh([nxt])))[0, 0]
        return seq

    group_refs = [ref_generate(ids) for ids in group_prompts]
    refs = [group_refs[g] for g in gid]

    eng = DecodeEngine(net, replicas=replicas, n_pages=n_pages,
                       page_tokens=page_tokens, max_batch=max_batch,
                       batch_window_ms=batch_window_ms,
                       speculative=int(speculative_k),
                       draft_net=draft_net)
    t0 = time.perf_counter()
    eng.warm()
    warmup_s = time.perf_counter() - t0

    # logit-level exactness spot check (token equality below could in
    # principle survive a small numeric drift; this cannot)
    net.rnn_clear_previous_state()
    ref_l = np.asarray(net.rnn_time_step(oh(prompts[0])))[0, -1]
    logits_exact = bool(np.array_equal(ref_l, eng.prefill("check",
                                                          prompts[0])))
    tok = int(np.argmax(ref_l))
    ref_l2 = np.asarray(net.rnn_time_step(oh([tok])))[0, 0]
    logits_exact &= bool(np.array_equal(ref_l2, eng.step("check", tok)))
    eng.close_session("check")
    net.rnn_clear_previous_state()
    snap = compile_snapshot()
    pre = eng.describe()   # so the spot check doesn't pollute run counters

    results: list = [None] * sessions
    step_times: list[float] = []
    errors: list[str] = []
    lock = threading.Lock()
    gate = threading.Event()

    def worker(i: int):
        ts: list[float] = []
        try:
            gate.wait()
            time.sleep(stagger_s * i)   # open arrival: staggered starts
            out = eng.generate(f"s{i}", prompts[i], gen_tokens,
                               step_times=ts)
            with lock:
                results[i] = out
        except Exception as e:
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            with lock:
                step_times.extend(ts)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(sessions)]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    gate.set()
    for t in threads:
        t.join(timeout=600.0)
    wall = time.perf_counter() - t0
    desc = eng.describe()
    cdelta = compile_delta(snap)
    eng.stop()
    if errors:
        return {"config": "transformer", "error": errors[0]}

    matched = sum(1 for i in range(sessions) if results[i] == refs[i])
    s = sorted(step_times)

    def pct(q):
        return round(
            1000.0 * s[min(len(s) - 1, int(round(q * (len(s) - 1))))], 3)

    hits, misses = desc["affinity_hits"], desc["affinity_misses"]
    spec: dict = {}
    if speculative_k:
        # run-delta accepted-tokens-per-step: tokens emitted per target
        # decode launch (plain steps emit 1; a verify round emits
        # 1 + its accepts) — the speculative speedup lever the budget
        # gates at > 1.0
        steps_run = ((desc["decode_steps"] - pre["decode_steps"])
                     + (desc["spec_rounds"] - pre["spec_rounds"]))
        acc_run = desc["spec_accepted"] - pre["spec_accepted"]
        spec = {
            "speculative_k": speculative_k,
            "spec_rounds": desc["spec_rounds"] - pre["spec_rounds"],
            "spec_proposed": desc["spec_proposed"] - pre["spec_proposed"],
            "spec_accepted": acc_run,
            "spec_rejected": desc["spec_rejected"] - pre["spec_rejected"],
            "spec_accept_tokens_per_step":
                round((steps_run + acc_run) / steps_run, 4)
                if steps_run else None,
            "spec_draft_truncations": desc.get("spec_draft_truncations"),
        }
    return {
        "config": "transformer",
        "model": f"gpt_mini vocab{vocab} w{width} L{n_layers} "
                 f"h{n_heads} f32 (cache {max_cache_len})",
        "platform": _platform(),
        "sessions": sessions, "gen_tokens": gen_tokens,
        "replicas": replicas,
        "prompt_lens": sorted(len(p) for p in prompts),
        "prompt_groups": n_groups,
        "shared_prefix_tokens": shared_prefix,
        "arrival_stagger_s": stagger_s,
        "warmup_s": round(warmup_s, 3),
        "wall_s": round(wall, 3),
        "decode_tokens_per_sec": round(sessions * gen_tokens / wall, 1),
        "inter_token_p50_ms": pct(0.50),
        "inter_token_p99_ms": pct(0.99),
        "decode_bit_identical":
            1 if (matched == sessions and logits_exact) else 0,
        "sessions_matched": matched,
        "logits_exact": logits_exact,
        "kv_pool_occupancy": round(desc["occupancy"], 4),
        "kv_pool_pages": desc["n_pages"],
        "kv_page_tokens": desc["page_tokens"],
        "kv_evictions": desc["evictions"],
        "reprefills": desc["reprefills"],
        "decode_steps": desc["decode_steps"],
        # -- chunked prefill + prefix sharing (the r02 arm's raison d'etre);
        #    counters are run-deltas so the warm-up spot check stays out
        "prefill_chunk_tokens": desc["prefill_chunk_tokens"],
        "prefill_chunks": desc["prefill_chunks"] - pre["prefill_chunks"],
        "chunked_prefills":
            desc["chunked_prefills"] - pre["chunked_prefills"],
        "interleaved_prefills":
            desc["interleaved_prefills"] - pre["interleaved_prefills"],
        "chunk_interleave_ratio":
            round((desc["interleaved_prefills"]
                   - pre["interleaved_prefills"])
                  / (desc["chunked_prefills"] - pre["chunked_prefills"]), 4)
            if desc["chunked_prefills"] > pre["chunked_prefills"] else None,
        "prefix_hits": desc["prefix_hits"] - pre["prefix_hits"],
        "shared_prompt_tokens":
            desc["shared_tokens"] - pre["shared_tokens"],
        "kv_shared_pages": desc["shared_pages"],
        "kv_store_pages": desc["store_pages"],
        "kv_logical_pages": desc["logical_pages"],
        "pool_dedup_ratio": desc["dedup_ratio"],
        "compile_delta_after_warm": cdelta["count"],
        "affinity_hit_rate": round(hits / (hits + misses), 4)
        if hits + misses else None,
        **spec,
    }


def _fit_copy_lm(net, vocab: int = 32, steps: int = 80, batch: int = 8,
                 seq: int = 32, max_run: int = 5, seed: int = 0) -> int:
    """Briefly fit ``net`` on a run-structured copy task: sequences are
    short constant runs, so "next token = current token" is usually
    right. Fitting BOTH the target and the draft on this makes their
    greedy continuations genuinely correlate — the speculative bench's
    acceptance rate is then measured, not assumed (random-weight models
    would agree only by 1/vocab chance)."""
    from deeplearning4j_tpu.datasets import DataSet
    rng = np.random.default_rng(seed)
    rows = np.arange(seq)
    for _ in range(steps):
        toks = np.empty((batch, seq), np.int64)
        for b in range(batch):
            pos = 0
            while pos < seq:
                t = int(rng.integers(0, vocab))
                end = min(seq, pos + int(rng.integers(2, max_run + 1)))
                toks[b, pos:end] = t
                pos = end
        x = np.zeros((batch, seq, vocab), np.float32)
        y = np.zeros((batch, seq, vocab), np.float32)
        for b in range(batch):
            x[b, rows, toks[b]] = 1.0
            y[b, rows, np.concatenate([toks[b, 1:], toks[b, :1]])] = 1.0
        net.fit_batch(DataSet(x, y))
    return steps


def bench_decode_speculative(sessions: int = 12, gen_tokens: int = 24,
                             spec_k: int = 3, fit_steps: int = 80,
                             **kw) -> dict:
    """The TRANSFORMER_r03 arm: the r02 mixed open-arrival decode load
    with chunked prefill + COW prefix sharing + SPECULATIVE DECODING all
    on. Builds a copy-task-trained gpt_mini target and gpt_mini_draft
    draft (same vocab, half width, one layer), runs the r02 load once
    with speculation OFF and once with it ON (same trained nets, same
    prompts), and publishes the comparison: accepted-tokens-per-step,
    tokens/sec vs the off arm, and the bit-identity verdict for the
    fully stacked path (check_budgets gates
    ``min_spec_accept_tokens_per_step`` and ``min_spec_bit_identical``
    on this receipt)."""
    from deeplearning4j_tpu.zoo import F32, gpt_mini, gpt_mini_draft

    vocab, cache = 32, 128
    target = gpt_mini(vocab_size=vocab, width=64, n_layers=2, n_heads=4,
                      max_len=cache, max_cache_len=cache, dtype=F32)
    draft = gpt_mini_draft(vocab_size=vocab, width=32, n_layers=1,
                           n_heads=2, max_len=cache, max_cache_len=cache,
                           dtype=F32)
    _fit_copy_lm(target, vocab=vocab, steps=fit_steps)
    _fit_copy_lm(draft, vocab=vocab, steps=fit_steps)

    off = bench_decode(sessions=sessions, gen_tokens=gen_tokens,
                       net=target, **kw)
    if "error" in off:
        return off
    on = bench_decode(sessions=sessions, gen_tokens=gen_tokens,
                      net=target, speculative_k=spec_k, draft_net=draft,
                      **kw)
    if "error" in on:
        return on
    on["model"] += " [copy-task-trained]"
    on["draft_model"] = (f"gpt_mini_draft vocab{vocab} w32 L1 h2 f32 "
                         f"(cache {cache})")
    on["copy_fit_steps"] = fit_steps
    on["spec_off_tokens_per_sec"] = off["decode_tokens_per_sec"]
    on["spec_speedup_vs_off"] = (
        round(on["decode_tokens_per_sec"] / off["decode_tokens_per_sec"], 4)
        if off["decode_tokens_per_sec"] else None)
    # bit-identity for the fully stacked path (chunking + sharing +
    # speculation): same check as r02's, named so the budget gate can
    # pin it independently
    on["spec_bit_identical"] = on["decode_bit_identical"]
    return on


# ------------------------------------------------------------- fleet bench
def run_load_inproc(server, x: np.ndarray, reference: np.ndarray,
                    clients: int, requests_per_client: int,
                    rows_per_request: int = 4) -> dict:
    """Closed-loop clients over ``server.predict`` directly (no HTTP).
    The replica-scaling question is about the DISPATCH tier — admission,
    routing, N device threads — and this container has one CPU core, so
    per-request HTTP/JSON handling would be pure serial overhead that
    caps any measured scaling long before the replica tier does. Every
    reply is still checked bit-identical against the reference rows."""
    lats: list[float] = []
    lock = threading.Lock()
    errors: list[str] = []
    mismatches = [0]
    start_gate = threading.Event()
    k = rows_per_request

    def client(tid: int):
        my_lats = []
        try:
            start_gate.wait()
            for r in range(requests_per_client):
                i = ((tid * requests_per_client + r) * k) % (x.shape[0] - k)
                t0 = time.perf_counter()
                got = np.asarray(server.predict(x[i:i + k]))
                my_lats.append(time.perf_counter() - t0)
                if not np.array_equal(got, reference[i:i + k]):
                    with lock:
                        mismatches[0] += 1
        except Exception as e:
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            with lock:
                lats.extend(my_lats)

    threads = [threading.Thread(target=client, args=(t,), daemon=True)
               for t in range(clients)]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    start_gate.set()
    for t in threads:
        t.join(timeout=600.0)
    wall = time.perf_counter() - t0
    if errors:
        return {"error": errors[0], "clients": clients}
    total = clients * requests_per_client
    s = sorted(lats)

    def pct(q):
        return round(1000.0 * s[min(len(s) - 1, int(round(q * (len(s) - 1))))],
                     3)

    return {
        "clients": clients,
        "requests": total,
        "rows_per_request": k,
        "rows_per_sec": round(total * k / wall, 1),
        "wall_s": round(wall, 3),
        "p50_ms": pct(0.50), "p95_ms": pct(0.95), "p99_ms": pct(0.99),
        "bit_identical": mismatches[0] == 0,
        "mismatched_requests": mismatches[0],
    }


def bench_fleet(replicas=(1, 2, 4), device_sim_ms: float = 20.0,
                clients: int = 128, requests_per_client: int = 8,
                max_batch: int = 8, hidden: int = 64) -> dict:
    """Rows/sec vs replica count on SIMULATED devices. Each replica's
    forward runs the real (tiny) model for row correctness, then sleeps
    ``device_sim_ms`` with the GIL released — the sleep stands in for an
    accelerator executing the bucket, so N device threads model N
    accelerators draining in parallel even on this 1-core host. The
    published scaling number measures the dispatch tier (global
    admission + queue-depth routing + N device threads), which is
    exactly the subsystem this sweep exists to gate."""
    from deeplearning4j_tpu.serving.server import ModelServer

    net = _serving_mlp(hidden=hidden, depth=2)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 64)).astype(np.float32)
    reference = np.asarray(net.output(x))

    report: dict = {"device_sim_ms": device_sim_ms, "max_batch": max_batch,
                    "clients": clients,
                    "transport": "in-process closed-loop predict() "
                                 "(see run_load_inproc)",
                    "replica_sweep": {}}
    for r in replicas:
        server = ModelServer(net, port=0, max_batch=max_batch,
                             batch_window_ms=1.0, max_queue=4096,
                             replicas=r)
        real = server._device_forward

        def simulated(feats, _real=real):
            out = _real(feats)
            np.asarray(out)             # block until real compute lands
            time.sleep(device_sim_ms / 1000.0)  # the simulated device
            return out

        for rep in server.fleet.replicas:
            rep.batcher._forward = simulated
        server._fleet.warm([(64,)])
        try:
            res = run_load_inproc(server, x, reference, clients,
                                  requests_per_client)
            res["requeued"] = server.fleet.requeued
            report["replica_sweep"][f"r{r}"] = res
        finally:
            server.stop()
    r1 = report["replica_sweep"].get("r1", {}).get("rows_per_sec")
    r4 = report["replica_sweep"].get("r4", {}).get("rows_per_sec")
    if r1 and r4:
        report["replica_scaling"] = round(r4 / r1, 2)
    return report


def bench_mesh(hidden: int = 128, depth: int = 3, concurrency: int = 16,
               requests_per_client: int = 10, max_batch: int = 32) -> dict:
    """Tensor-parallel f32 serving over HTTP against the 8-device mesh:
    every reply row must be bit-identical to the single-device
    ``net.output()`` reference computed BEFORE the params were sharded.
    ``hidden`` stays under 256 so XLA:CPU blocks the local gemm's K loop
    identically at sharded and full width (SERVING.md "Fleet" — on TPU
    the MXU K loop is width-independent)."""
    import jax

    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.serving import serve

    n_dev = len(jax.devices())
    net = _serving_mlp(hidden=hidden, depth=depth)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 64)).astype(np.float32)
    reference = np.asarray(net.output(x))   # pre-shard, single-device

    mesh = make_mesh({"model": n_dev})
    server = serve(net, port=0, max_batch=max_batch, batch_window_ms=1.0,
                   mesh=mesh)
    try:
        res = run_load(server.port, x, reference, concurrency,
                       requests_per_client)
    finally:
        server.stop()
    res.update({"mesh_axes": f"model:{n_dev}",
                "model": f"serving_mlp 64-{hidden}x{depth}-10 f32"})
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=25,
                    help="requests per client (per concurrency level)")
    ap.add_argument("--concurrency", type=int, nargs="+",
                    default=[1, 8, 64])
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--batch-window-ms", type=float, default=2.0)
    ap.add_argument("--hidden", type=int, default=4096)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="small fast run (bench.py integration)")
    ap.add_argument("--fleet", action="store_true",
                    help="replica-tier scaling sweep on simulated devices"
                         " + mesh bit-identity check (config "
                         "serving_fleet, gated by check_budgets)")
    ap.add_argument("--mesh", action="store_true",
                    help="only the tensor-parallel bit-identity serve")
    ap.add_argument("--decode", action="store_true",
                    help="mixed prefill/decode open-arrival load over the "
                         "DecodeEngine fleet: heavy-tailed prompts, shared "
                         "system prefix, chunked prefill + COW prefix "
                         "sharing on (config transformer; the "
                         "TRANSFORMER_r02.json receipt, gated by "
                         "check_budgets)")
    ap.add_argument("--speculative", action="store_true",
                    help="with --decode: the TRANSFORMER_r03 arm — "
                         "copy-task-trained target + gpt_mini_draft, "
                         "speculation off then on over the same r02 "
                         "load, accepted-tokens/step and tokens/sec "
                         "comparison (gated by check_budgets)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft tokens proposed per speculative round "
                         "(--decode --speculative)")
    ap.add_argument("--sessions", type=int, default=12,
                    help="concurrent decode sessions (--decode)")
    ap.add_argument("--gen-tokens", type=int, default=24,
                    help="greedy tokens generated per session (--decode)")
    ap.add_argument("--no-train", action="store_true",
                    help="skip the gpt_mini training-MFU entry in the "
                         "--decode report")
    ap.add_argument("--replicas", type=int, nargs="+", default=[1, 2, 4],
                    help="fleet sweep replica counts")
    ap.add_argument("--device-sim-ms", type=float, default=20.0,
                    help="simulated per-bucket device time (fleet sweep)")
    ap.add_argument("--clients", type=int, default=128,
                    help="closed-loop clients in the fleet sweep (on a "
                         "1-core host more threads just add GIL churn; "
                         "raise this on real machines)")
    ap.add_argument("--out", metavar="OUT.json", default=None,
                    help="also write the report to this file "
                         "(consumed by scripts/perf_probe.py --serving-results"
                         " and scripts/check_budgets.py)")
    args = ap.parse_args()
    if args.quick:
        args.concurrency, args.requests = [16], 10
    if args.decode:
        if args.speculative:
            report = bench_decode_speculative(sessions=args.sessions,
                                              gen_tokens=args.gen_tokens,
                                              spec_k=args.spec_k)
        else:
            report = bench_decode(sessions=args.sessions,
                                  gen_tokens=args.gen_tokens)
        if not args.no_train and "error" not in report:
            # the training side of the workload: gpt_mini fit step with
            # the XLA-cost-model FLOPs ledger (bench.py `transformer`) —
            # train_mfu is hoisted flat so the budget gate sees it
            import importlib.util
            path = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "bench.py")
            spec = importlib.util.spec_from_file_location("bench", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            train = mod.run_config("transformer")
            report["train"] = train
            if train.get("mfu") is not None:
                report["train_mfu"] = train["mfu"]
            if train.get("tokens_per_sec") is not None:
                report["train_tokens_per_sec"] = train["tokens_per_sec"]
    elif args.fleet or args.mesh:
        # BEFORE any deeplearning4j_tpu/jax import: the fleet story is
        # "8 simulated devices" — force the host platform to expose them
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        report = {"config": "serving_fleet", "platform": _platform()}
        if args.fleet:
            report.update(bench_fleet(tuple(args.replicas),
                                      args.device_sim_ms, args.clients,
                                      max_batch=args.max_batch
                                      if args.max_batch != 64 else 8))
        report["mesh"] = bench_mesh()
    else:
        report = bench_serving(tuple(args.concurrency), args.requests,
                               args.max_batch, args.batch_window_ms,
                               args.hidden, args.depth)
    print(json.dumps(report, indent=2))
    if args.out:
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        os.replace(tmp, args.out)


if __name__ == "__main__":
    main()
