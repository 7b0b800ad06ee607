"""Chip smoke: the quickest proof that fit and serve still start on a TPU.

    python3 chip_smoke.py

One process, sequential phases, seeded synthetic data, no network, no
git. Each phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
and the exit code is 0 only when every phase passed. Without a TPU
(``JAX_PLATFORMS=cpu``, or no accelerator found) it exits 2 at the device
phase, prints no result, and runs nothing on the CPU.

Phases (sizes are arguments so a scratch driver can walk the same code at
a toy size; the defaults are the contract):

- device   — platform is ``tpu``; the peak-FLOP/s table knows the device.
- train    — ``zoo.resnet50()`` bf16, 224x224, 1000 classes, batch 256:
  ``net.fit`` over 16 batches with DEFAULT arguments (the "auto" path:
  multi_step 8 + device prefetch), then repeated ``fit_batch``.
- kernels  — ``zoo.char_rnn()`` at b=32 and b=256 (the Pallas LSTM must be
  in the lowered step), then both Pallas kernels against their XLA
  references, forward and gradient, at the bench shapes: 64 timesteps
  (four to a grid step) and 50 (two).
- serve    — ``ModelServer`` + ``DecodeEngine`` over ``zoo.gpt_mini`` f32:
  8 concurrent ``/predict`` and 2 ``/decode`` sessions over HTTP.
- data-parallel — with >= 4 devices: ResNet-50 on a 4-way data mesh.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at
``<checkout>/.jax_cache`` (exported below, before jax is imported, so no
code path sets a directory). Each phase reports its backend-compile
seconds and cache hits, which is how a warm second run shows itself.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
import urllib.request

_REPO = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(_REPO, ".jax_cache"))

import numpy as np  # noqa: E402

# Written tolerances. bf16 kernel bounds are the TPU-gated classes' in
# tests/test_backend_equivalence.py; the serving ones were settled on the
# v5e (PERF.md Findings, PR 21, has what was observed under each).
KERNEL_FWD_TOL = 0.05       # abs+rel, bf16 forward vs the XLA reference
KERNEL_GRAD_TOL = 0.1       # max|diff| / max|ref|, bf16 gradients
# /predict probabilities vs net.output of the same row alone. Not 1e-5:
# net.output's f32 dots run at the TPU's default precision (one bf16
# pass) and XLA picks another algorithm per batch shape, so a row served
# in a coalesced bucket sat 3.0e-3 from the same row at batch 1 (9.0e-3
# from real-f32 matmuls; top probabilities reach 0.95). A row scattered
# to the wrong request is off by ~1e-1.
PREDICT_ATOL = 1e-2
# /decode probabilities vs the real-f32 full forward: the streaming path
# multiplies and reduces in f32 on the VPU; 6e-7 observed.
DECODE_ATOL = 1e-5
DP_SCORE_RTOL = 5e-2        # 4-way data-parallel vs single-device score


class NoAccelerator(RuntimeError):
    """jax found no TPU: nothing may run, nothing may be reported."""


def _check(cond, what: str) -> None:
    # not `assert`: the smoke must hold under `python -O` too
    if not cond:
        raise AssertionError(what)


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    _check(np.all(np.isfinite(got)), "non-finite kernel output")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-3))


def _onehot(ids, vocab: int) -> np.ndarray:
    return np.eye(vocab, dtype=np.float32)[np.asarray(ids)]


# ------------------------------------------------------------------ device
def phase_device() -> dict:
    import jax
    import jaxlib
    from importlib.metadata import version

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise NoAccelerator(
            f"jax found platform {dev.platform!r} ({dev.device_kind}), "
            "not a TPU")
    from deeplearning4j_tpu.utils.perf import peak_flops

    _check("DL4J_TPU_PEAK_FLOPS" not in os.environ,
           "DL4J_TPU_PEAK_FLOPS is set: the peak must come from the table")
    peak = peak_flops(dev)
    _check(peak, f"device kind {dev.device_kind!r} is not in PEAK_FLOPS")
    _check(os.environ.get("DL4J_TPU_PALLAS_INTERPRET") != "1",
           "DL4J_TPU_PALLAS_INTERPRET=1: kernels would not be compiled")
    return {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": version("libtpu"), "peak_flops": peak,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }


# ------------------------------------------------------------------- train
def _image_batches(n_batches, batch, image, classes, seed=0):
    """Seeded uint8 noise images standardized to zero mean and unit
    variance + random one-hot labels (uint8 draws are ~10x faster than
    float normals at 154 MB a batch)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        x = rng.integers(0, 256, (batch, image, image, 3), dtype=np.uint8)
        y = _onehot(rng.integers(0, classes, batch), classes)
        out.append(DataSet((x.astype(np.float32) - 127.5) / 73.9, y))
    return out


def _on_tpu(tree) -> bool:
    import jax
    return all(d.platform == "tpu"
               for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices())


def phase_train(batch=256, image=224, classes=1000, n_batches=16,
                repeat_steps=8) -> dict:
    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.observability import metrics as obs

    net = zoo.resnet50(n_classes=classes, image_size=image)
    _check(net.conf.global_conf.dtype.compute_dtype == "bfloat16",
           "resnet50 is not under the bf16 policy")
    # the never-run-on-chip "auto" path must be the one fit() takes
    chunk = net._resolve_multi_step("auto")
    _check(chunk == 8, f"multi_step auto resolved to {chunk}, not 8")
    _check(net._resolve_device_prefetch("auto"), "device prefetch is off")

    batches = _image_batches(n_batches, batch, image, classes)
    t0 = time.perf_counter()
    net.fit(ListDataSetIterator(batches), epochs=1)   # default arguments
    fit_score = float(net.score_value)
    fit_s = time.perf_counter() - t0
    rep = net.last_run_report
    _check(rep.status == "completed" and rep.steps == n_batches,
           f"fit report: status={rep.status} steps={rep.steps}")
    _check(np.isfinite(fit_score), f"fit score {fit_score}")
    _check(rep.flops_per_step and rep.mfu,
           f"auto-derived flops_per_step={rep.flops_per_step} "
           f"mfu={rep.mfu}")
    marks = obs.memory_watermarks()
    _check(marks and all(k.startswith("tpu:") for k in marks),
           f"memory watermark sources {sorted(marks)} are not tpu:*")
    _check(rep.device_memory_peak_bytes
           and rep.device_memory_peak_bytes <= max(marks.values()),
           f"report watermark {rep.device_memory_peak_bytes} vs {marks}")

    scores = [float(net.fit_batch(batches[0])) for _ in range(repeat_steps)]
    _check(all(np.isfinite(s) for s in scores), f"scores {scores}")
    _check(scores[-1] < scores[0], f"scores did not fall: {scores}")
    _check(_on_tpu(net.params), "params are not on a TPU device")
    return {
        "multi_step": chunk, "device_prefetch": True, "steps": rep.steps,
        "fit_s": round(fit_s, 2), "fit_score": round(fit_score, 4),
        "repeat_scores": [round(s, 4) for s in scores],
        "flops_per_step": rep.flops_per_step, "mfu_incl_compile": rep.mfu,
        "device_memory_peak_bytes": rep.device_memory_peak_bytes,
        "watermark_sources": sorted(marks),
    }


# ----------------------------------------------------------------- kernels
def _lstm_args(t, b, n, n_in, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    cd = jnp.bfloat16
    mask = (rng.random((t, b)) > 0.3).astype(np.float32)
    mask[0] = 1.0
    x, Wx, h0, c0, Wh, p = (
        jnp.asarray(rng.normal(0, 1.0, (t, b, n_in)), cd),
        jnp.asarray(rng.normal(0, 0.5 / np.sqrt(n_in), (n_in, 4 * n)), cd),
        jnp.asarray(rng.normal(0, 0.5, (b, n)), cd),
        jnp.asarray(rng.normal(0, 0.5, (b, n)), cd),
        jnp.asarray(rng.normal(0, 0.05, (n, 4 * n)), cd),
        jnp.asarray(rng.normal(0, 0.2, (3, n)), cd))
    bias = jnp.asarray(rng.normal(0, 0.3, (4 * n,)), cd)
    return x, Wx, bias, h0, c0, Wh, p, jnp.asarray(mask, cd)


def _lstm_scan_f32(*args):
    """The scan in f32 on the same (bf16-rounded) inputs. The kernels keep
    z, the gates and the carry in f32; the scan in bf16 rounds xz and every
    intermediate, and over 64 steps its cell state drifts from the f32
    scan by more (0.48 in one entry of cT at b=256, n_in=80) than the
    kernels differ from it (0.012)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import lstm as lstm_ops

    return lstm_ops.lstm_sequence_xla(*(a.astype(jnp.float32) for a in args))


def _weighted_sum(y):
    """Position-dependent weights: a permuted output changes the loss."""
    import jax.numpy as jnp

    w = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
    return jnp.sum(y.astype(jnp.float32) * w)


def _lstm_loss(outs):
    import jax.numpy as jnp

    y, hT, cT = outs
    return (_weighted_sum(y) + 2.0 * jnp.sum(jnp.sin(hT.astype(jnp.float32)))
            + 0.5 * jnp.sum(jnp.square(cT.astype(jnp.float32))))


def _kernel_vs_reference(name, kernel, ref, loss_of, args, argnums) -> dict:
    """Forward within KERNEL_FWD_TOL and every gradient within
    KERNEL_GRAD_TOL of the XLA reference, both jitted on the device."""
    import jax

    got, want = (jax.tree_util.tree_leaves(jax.jit(f)(*args))
                 for f in (kernel, ref))
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=KERNEL_FWD_TOL, atol=KERNEL_FWD_TOL)
    g_got, g_want = (jax.jit(jax.grad(lambda *a, f=f: loss_of(f(*a)),
                                      argnums=argnums))(*args)
                     for f in (kernel, ref))
    errs = [_rel_err(g, w) for g, w in zip(g_got, g_want)]
    _check(max(errs) < KERNEL_GRAD_TOL, f"{name} grad rel errs {errs}")
    return {"fwd_max_rel": max(_rel_err(g, w) for g, w in zip(got, want)),
            "grad_max_rel": max(errs)}


def _check_flash_kernel(b, t, h, dh) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import attention as attn_ops

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(0, 0.5, (b, t, h, dh)), jnp.bfloat16)
               for _ in range(3))
    _check(attn_ops.attention_supported(q, k, v),
           "flash gate refuses the bench shape")
    lowered = jax.jit(attn_ops._flash).lower(q, k, v).as_text()
    _check("tpu_custom_call" in lowered, "flash did not lower to Mosaic")
    return _kernel_vs_reference(
        "flash", attn_ops._flash, attn_ops.causal_mha_xla_dot,
        _weighted_sum, (q, k, v), (0, 1, 2))


def phase_kernels(hidden=512, seq=64, bptt_seq=50, batches=(32, 256),
                  vocab=80, fit_steps=4,
                  flash_shape=(2, 256, 4, 128)) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.ops import lstm as lstm_ops

    out = {"char_rnn": [], "lstm": [], "flash": None}
    for b in batches:
        net = zoo.char_rnn(vocab_size=vocab, hidden=hidden, n_layers=2)
        rng = np.random.default_rng(b)
        ds = DataSet(_onehot(rng.integers(0, vocab, (b, seq)), vocab),
                     _onehot(rng.integers(0, vocab, (b, seq)), vocab))
        net.fit(ListDataSetIterator([ds] * fit_steps), epochs=1)
        score = float(net.score_value)
        _check(np.isfinite(score), f"char_rnn b={b} score {score}")
        # the Pallas LSTM must be IN the step, compiled — not the silent
        # xla delegate behind ops/lstm.py's support gate
        step = net._train_step or net._build_train_step()
        lowered = step.lower(*net._step_args(
            net._batch_args(ds), jax.random.PRNGKey(0))).as_text()
        n_calls = lowered.count("tpu_custom_call")
        _check(n_calls > 0, f"char_rnn b={b}: no tpu_custom_call in step")
        out["char_rnn"].append({"b": b, "score": round(score, 4),
                                "tpu_custom_calls": n_calls})
        del net
        # 64 timesteps go four to a grid step, and 50 (the BPTT segment
        # of DL4J's example) two; one-hot-wide rows are projected by the
        # forward kernel, rows wider than the hidden state by a matmul
        # ahead of it
        for t, n_in in ((seq, vocab), (bptt_seq, 2 * hidden)):
            errs = _kernel_vs_reference(
                f"lstm b={b} t={t} n_in={n_in}", lstm_ops._lstm_seq_pallas,
                _lstm_scan_f32, _lstm_loss,
                _lstm_args(t, b, hidden, n_in), (0, 1, 2, 3, 4, 5, 6))
            out["lstm"].append({"b": b, "t": t, "n_in": n_in, **errs})
    out["flash"] = _check_flash_kernel(*flash_shape)
    return out


# ------------------------------------------------------------------- serve
def _post(url: str, payload: dict):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        return resp.status, json.loads(resp.read())


def _in_threads(fns):
    """Run thunks concurrently; re-raise the first failure."""
    errors, results = [], [None] * len(fns)

    def run(i, fn):
        try:
            results[i] = fn()
        except BaseException as e:  # surfaced below, on the caller
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i, fn), daemon=True)
               for i, fn in enumerate(fns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    _check(not any(t.is_alive() for t in threads), "client thread hung")
    if errors:
        raise errors[0]
    return results


def phase_serve(n_predict=8, predict_len=32, prompts=(12, 20), n_steps=16,
                ref_len=64, **net_kw) -> dict:
    import jax

    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.observability import metrics as obs
    from deeplearning4j_tpu.serving.decode import DecodeEngine
    from deeplearning4j_tpu.serving.server import ModelServer

    # three same-seeded nets: the server's, the engine's (it owns its
    # net's streaming flags), and an untouched one for the references
    net, eng_net, ref_net = (zoo.gpt_mini(dtype=zoo.F32, **net_kw)
                             for _ in range(3))
    vocab = int(net.layers[0].conf.n_in)
    rng = np.random.default_rng(7)
    xs = [_onehot(rng.integers(0, vocab, (1, predict_len)), vocab)
          for _ in range(n_predict)]
    want = [np.asarray(ref_net.output(x)) for x in xs]
    sessions = {f"s{i}": [int(t) for t in rng.integers(0, vocab, n)]
                for i, n in enumerate(prompts)}

    eng = DecodeEngine(eng_net)
    srv = ModelServer(net, decode_engine=eng, port=0,
                      input_shapes=[(predict_len, vocab)]).start()
    try:
        _check(srv.shapes_seen, "the /predict warm-up ladder did not run")
        _check(eng.warm(), "decode warm-up compiled nothing")
        snap = obs.compile_snapshot()

        def predict(i):
            st, out = _post(srv.url + "/predict",
                            {"features": xs[i].tolist()})
            _check(st == 200, f"/predict -> {st}")
            return np.asarray(out["predictions"], np.float32)

        got = _in_threads([lambda i=i: predict(i)
                           for i in range(n_predict)])
        predict_err = max(float(np.abs(g - w).max())
                          for g, w in zip(got, want))
        _check(predict_err <= PREDICT_ATOL,
               f"/predict off net.output by {predict_err}")

        def decode(sid):
            ids = list(sessions[sid])
            st, out = _post(srv.url + "/decode",
                            {"op": "prefill", "sid": sid, "ids": ids})
            _check(st == 200, f"/decode prefill -> {st}")
            rows, toks = [np.asarray(out["logits"], np.float32)], []
            for _ in range(n_steps):
                toks.append(int(np.argmax(rows[-1])))
                st, out = _post(srv.url + "/decode",
                                {"op": "step", "sid": sid, "ids": ids,
                                 "token": toks[-1]})
                _check(st == 200 and not out.get("recovered"),
                       f"/decode step -> {st} {out.get('recovered')}")
                ids.append(toks[-1])
                rows.append(np.asarray(out["logits"], np.float32))
            st, out = _post(srv.url + "/decode", {"op": "close", "sid": sid})
            _check(st == 200 and out["closed"], "/decode close failed")
            return toks, rows

        streams = dict(zip(sessions, _in_threads(
            [lambda s=s: decode(s) for s in sessions])))
        delta = obs.compile_delta(snap)
        _check(delta["count"] == 0,
               f"{delta['count']} compiles after warm-up: {delta}")
    finally:
        srv.stop()

    # reference 1 (gate): the float32 FULL forward, re-run on the whole
    # history at a fixed padded length (causal: later zeros cannot reach
    # an earlier row). On a TPU an f32 dot defaults to one bf16 pass, so
    # the reference asks for real f32 matmuls.
    decode_err, bit_identical = 0.0, True
    for sid, prompt in sessions.items():
        toks, rows = streams[sid]
        ids = list(prompt)
        for step, row in enumerate(rows):
            x = np.zeros((1, ref_len, vocab), np.float32)
            x[0, :len(ids)] = _onehot(ids, vocab)
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(ref_net.output(x))[0, len(ids) - 1]
            decode_err = max(decode_err, float(np.abs(row - ref).max()))
            if step < len(toks):
                _check(int(np.argmax(ref)) == toks[step],
                       f"{sid} token {step}: served {toks[step]}, full "
                       f"forward {int(np.argmax(ref))}")
                ids.append(toks[step])
        # reference 2 (observation, ROADMAP D1): the sequential
        # rnn_time_step stream the bit-identity contract is pinned on
        ref_net.rnn_clear_previous_state()
        seq = np.asarray(ref_net.rnn_time_step(
            _onehot(prompt, vocab)[None]))[0, -1]
        for step, row in enumerate(rows):
            bit_identical &= bool(np.array_equal(row, seq))
            if step < len(toks):
                seq = np.asarray(ref_net.rnn_time_step(
                    _onehot([toks[step]], vocab)[None]))[0, -1]
        ref_net.rnn_clear_previous_state()
    _check(decode_err <= DECODE_ATOL,
           f"/decode off the f32 full forward by {decode_err}")
    return {
        "predict_requests": n_predict, "predict_max_abs_err": predict_err,
        "predict_bit_identical": predict_err == 0.0,
        "decode_sessions": len(sessions), "decode_steps": n_steps,
        "decode_max_abs_err": decode_err, "greedy_tokens_equal": True,
        "compile_delta_after_warm": delta["count"],
        "bit_identical": bit_identical,
    }


# ----------------------------------------------------------- data-parallel
def _bytes_in_use(devices) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def phase_data_parallel(n_dev=4, batch=256, image=224, classes=1000,
                        fit_steps=3) -> dict:
    import jax

    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    if jax.device_count() < n_dev:
        return {"multichip": f"skipped, {jax.device_count()} device"}
    devices = jax.devices()[:n_dev]
    batches = _image_batches(fit_steps + 1, batch, image, classes, seed=1)

    single = zoo.resnet50(n_classes=classes, image_size=image)
    single_score = float(single.fit_batch(batches[0]))
    del single
    gc.collect()

    before = _bytes_in_use(devices)
    net = zoo.resnet50(n_classes=classes, image_size=image)
    net.use_mesh(make_mesh({"data": n_dev}, devices=devices))
    mesh_score = float(net.fit_batch(batches[0]))
    _check(abs(mesh_score - single_score)
           <= DP_SCORE_RTOL * abs(single_score),
           f"first-step score: mesh {mesh_score} vs single {single_score}")
    net.fit(ListDataSetIterator(batches[1:]), epochs=1)
    last = float(net.score_value)
    _check(np.isfinite(last), f"mesh fit score {last}")

    # code that only ever saw virtual CPU devices may put it all on one
    param_devs = {d for leaf in jax.tree_util.tree_leaves(net.params)
                  for d in leaf.devices()}
    _check(param_devs == set(devices),
           f"params span {sorted(d.id for d in param_devs)}")
    x = jax.device_put(batches[0].features, net._prefetch_sharding())
    shards = {s.device: s.data.shape[0] for s in x.addressable_shards}
    _check(set(shards) == set(devices)
           and set(shards.values()) == {batch // n_dev},
           f"batch shards {shards}")
    after = _bytes_in_use(devices)
    _check(all(a > b for a, b in zip(after, before)),
           f"bytes_in_use did not grow on every device: {before}->{after}")
    return {"multichip": f"{n_dev} devices", "single_score": single_score,
            "mesh_first_score": mesh_score, "mesh_last_score": last,
            "bytes_in_use_grew": [a - b for a, b in zip(after, before)]}


# -------------------------------------------------------------------- main
def _run_phase(name: str, fn) -> dict:
    """Run one phase, print its JSON line, and drop what it built: the
    ResNet-50 nets leave ~9 GB resident, more than the next phase can
    share the chip with."""
    from deeplearning4j_tpu.observability import metrics as obs

    snap = obs.compile_snapshot()
    t0 = time.perf_counter()
    line = {"phase": name, "ok": True}
    try:
        line.update(fn())
    except NoAccelerator:
        raise
    except Exception as e:  # a phase failure fails the run, below
        import traceback
        traceback.print_exc()
        line.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
    delta = obs.compile_delta(snap)
    line.update(wall_s=round(time.perf_counter() - t0, 2),
                compiles=delta["count"],
                compile_s=round(delta["seconds"], 2),
                cache_hits=delta["cache_hits"],
                cache_misses=delta["cache_misses"])
    print(json.dumps(line), flush=True)
    gc.collect()
    return line


def main() -> int:
    try:
        device = _run_phase("device", phase_device)
    except NoAccelerator as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    # zero jax's persist floors BEFORE the first net is built, so the
    # many sub-second init programs are cached too (fit and the server
    # would only do this once they start)
    from deeplearning4j_tpu.compilecache import ensure_configured
    _check(ensure_configured(), "the compile cache did not activate")
    lines = [device] + [_run_phase(name, fn) for name, fn in (
        ("train", phase_train), ("kernels", phase_kernels),
        ("serve", phase_serve), ("data-parallel", phase_data_parallel))]
    failed = [ln["phase"] for ln in lines if not ln["ok"]]
    print(json.dumps({
        "phase": "summary", "failed": failed,
        "wall_s": round(sum(ln["wall_s"] for ln in lines), 2),
        "compile_s": round(sum(ln["compile_s"] for ln in lines), 2),
        "cache_hits": sum(ln["cache_hits"] for ln in lines),
        "cache_misses": sum(ln["cache_misses"] for ln in lines),
        "claim": None}), flush=True)
    # the contract line, last on stdout: {"ok": ..., "device": {...}}
    print(json.dumps({"ok": not failed, "device": device["device"]}),
          flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
