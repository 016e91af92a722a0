"""Benchmark entry point.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints one JSON result as its last stdout line.  It needs the program
(``src/``) beside ``bench/``; without it, or without the chips the cell
asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

# The paged tree-attention kernel's VMEM blocks grow with the query rows it
# takes at once; a prefix-hit suffix prefill of a GQA model in the longest
# suffix buckets needs more than the compiler's default 16 MiB of scoped
# VMEM (qwen2-1.5b, 1152 tokens: 23.2 MiB).  Added to whatever the machine
# already passes to the TPU runtime, never in its place.
VMEM_FLAG = "--xla_tpu_scoped_vmem_limit_kib=32768"
if VMEM_FLAG not in os.environ.get("LIBTPU_INIT_ARGS", ""):
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "") + " " + VMEM_FLAG).strip()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))
