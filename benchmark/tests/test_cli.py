"""``benchmark/run.py`` as the driver starts it: what it refuses, and the
shape of its last line (on the CPU, by ``--rehearse``)."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

CELL = "char_rnn-train-b256-t1024"


def _run(root, *args, **env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def test_without_a_tpu_nothing_is_reported():
    p = _run(ROOT, "--workload", CELL, "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode == 3 and p.stdout == ""
    assert "not a TPU" in p.stderr


def test_an_unknown_workload_is_a_manifest_error():
    p = _run(ROOT, "--workload", "nope", "--seconds", "1")
    assert p.returncode == 2 and p.stdout == ""


def test_alone_in_a_directory_it_refuses(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), "--workload", CELL, "--seconds", "1")
    assert p.returncode == 4 and p.stdout == ""


def test_rehearsal_walks_the_flow_and_reports_no_metric():
    p = _run(ROOT, "--workload", CELL, "--seed", "3", "--seconds", "2",
             "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    last = lines[-1]
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    readings = next(ln["readings"] for ln in lines[:-1]
                    if ln.get("rehearsal") and "readings" in ln)
    assert readings and all(k.startswith("rehearsal:") for k in readings)
    # the chip's fit path: chunks of 8 steps, nothing compiled in the window
    assert readings["rehearsal:fit_steps_per_dispatch"] == 8
    assert readings["rehearsal:window_compiles"] == 0
    # no device plane in a CPU trace: the trace readers returned nothing
    assert "rehearsal:step_device_ms" not in readings
