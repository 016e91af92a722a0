"""``correct`` holds every replica of a fleet cell to the reference.

The fleet cell rehearses on four CPU devices, in a process of its own
(the device count is fixed when JAX starts).  A token altered where it is
produced, on every replica but the first, must turn ``correct`` false: the
requests compared are drawn across all replicas, not from one.  The sound
run is ``test_run.py::test_cpu_rehearsal``."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FAULTY_RUN = """
import json, sys, time
sys.path.insert(0, "src")
sys.path.insert(0, "bench")
import jax
import jax.numpy as jnp
import repro.models.transformer as tx
from harness import main
from harness.main import CompileClock, run_once
from harness.spec import load_cell

first = jax.devices()[0]
orig = tx.verify_accept_device

def bad(tokens, parent, n_live, chosen):
    # traced once per replica, under that replica's device
    if jax.config.jax_default_device not in (None, first):
        chosen = jnp.where(chosen > 2, chosen - 1, chosen + 1)
    return orig(tokens, parent, n_live, chosen)

tx.verify_accept_device = bad
main.REHEARSAL["correct"] = dict(main.REHEARSAL["correct"], requests=16)
cell = load_cell("qwen2-1.5b.fleet4.rag")
res = run_once(cell, 23, 4.0, False, jax.devices(), CompileClock(),
               time.perf_counter(), rehearse=True)
print(json.dumps(res))
"""


def test_a_fault_on_the_other_replicas_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FAULTY_RUN], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["rehearsal"]["replicas"] == 4
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]
