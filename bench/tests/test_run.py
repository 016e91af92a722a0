"""The entry point: it refuses without a TPU, refuses without the program
beside it, and rehearses every cell on the CPU through the same code path
without printing a device metric."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
CHIPS = {w["name"]: int(w["chips"]) for w in BENCH["workloads"]}


def run(args, cwd=ROOT, timeout=900, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        # a cell of several chips rehearses on as many CPU devices
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={devices}").strip()
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_refuses_without_a_tpu():
    p = run(["--workload", CELLS[0], "--seed", "3000000001", "--seconds",
             "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0", "--cpu-rehearsal"], cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_cpu_rehearsal(cell):
    """Every cell, replicas and all: as many replicas as the cell has
    chips, on as many CPU devices; every per-layer metric the program
    counts is read; nothing compiles in the window."""
    p = run(["--workload", cell, "--seed", "4000000019", "--seconds", "4",
             "--trace", "1", "--cpu-rehearsal"], devices=CHIPS[cell])
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["rehearsal"]["compiles_in_window"] == 0
    assert res["rehearsal"]["replicas"] == CHIPS[cell]
    counted = [m["name"] for m in BENCH["per_layer"]
               if m["source"] == "program_counter"
               and cell in m.get("workloads", CELLS)]
    assert counted and all(res["rehearsal"]["program_counters"][m]
                           is not None for m in counted)
    assert "metrics" not in res and "device" not in res
    assert list(res)[-1] == "checks"
