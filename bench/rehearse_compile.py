"""Compile a cell's serving programs for a described TPU v5e, on the CPU.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py \
        --workload <name> [<name> ...] [--n-blocks N]

Lowers and compiles, at each cell's real shapes, every program its window
can run (first-cohort prefill, slot prefill, the largest suffix bucket,
the fused tree-verify step) for one chip of a described ``v5e:2x2``
topology, with the Pallas kernels compiled (not interpreted).  The paged
pool has the program's own shape (its ``init_cache``).  It prints each
program's ``memory_analysis()`` and the pool that fits in one chip's HBM
beside the weights and the largest program's temporaries; a cell with
replicas holds one replica, the same programs, on each chip.  The steps
update the pool in place, so no copy of it is counted.  Nothing runs, so
nothing here is a time or a chip measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

import run  # noqa: E402,F401  (the TPU runtime flags the runs use)

HBM = 16 * 1024 ** 3
MARGIN = 1024 ** 3          # runtime, fragmentation and host transfers


def rehearse(name: str, n_blocks=None, only=None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness.main import engine_config
    from harness.model import Guide, bias_size, make_bias
    from harness.spec import family_module, load_cell
    from repro.serving.api import build_session_fns

    cell = load_cell(name)
    fam = family_module(cell.config)
    s = dict(cell.settings)
    if n_blocks:
        s["n_blocks"] = n_blocks
    shape = fam.shape_of(cell.config)
    ecfg = engine_config(s)
    tcfg = fam.program_config(shape, context=shape["context"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(lambda: fam.make_weights(shape, 0)))
    bias = None
    if cell.traffic.get("guide"):
        g = cell.traffic["guide"]
        bias = make_bias(Guide.of(g, shape["vocab"]),
                         bias_size(shape, float(g["bias_sigmas"])))
    fns = build_session_fns(ecfg, tcfg, params, logits_transform=bias)
    B, W, P = ecfg.lanes, ecfg.slots, ecfg.prefill_len
    nb = int(s["n_blocks"])
    # the program's own pool: (L, n_blocks, block_size, K * lane_dh)
    cache = on_chip(jax.eval_shape(lambda: fns.init_cache(B)))
    bpl = cache["block_tables"].shape[1]
    i32 = jnp.int32

    def sd(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one)

    def lanes(n):
        return {"greedy": sd((n,), jnp.bool_), "temp": sd((n,), jnp.float32),
                "seed": sd((n,), jnp.uint32)}

    big = fns.suffix_buckets[-1]
    progs = {
        "prefill": (fns.prefill._jitted,
                    (params, sd((B, P), i32), sd((B,), i32),
                     sd((B, bpl), i32), lanes(B))),
        "prefill_into_slot": (fns.prefill_into_slot._jitted,
                              (params, cache, sd((), i32), sd((1, P), i32),
                               sd((1,), i32), lanes(1))),
        f"prefill_suffix[{big}]": (fns.prefill_suffix._jitted,
                                   (params, cache, sd((), i32),
                                    sd((1, big), i32), sd((1,), i32),
                                    sd((1,), i32), lanes(1))),
        "fused_step": (fns.fused_step._jitted,
                       (params, cache, sd((B,), i32), sd((B, W), i32),
                        sd((B, W), i32), sd((B, W, W), jnp.bool_),
                        sd((B, W), i32), sd((B,), i32), lanes(B))),
    }
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    pool = sum(cache[k].size * cache[k].dtype.itemsize for k in ("k", "v"))
    block = pool // nb
    replicas = int((s.get("replicas") or {}).get("n", 1))
    out = {"workload": cell.name, "chips": replicas,
           "weights_bytes": weights, "block_bytes": block, "n_blocks": nb,
           "pool_bytes": pool, "programs": {}}
    worst = 0
    for prog, (fn, a) in progs.items():
        if only and prog.split("[")[0] not in only:
            continue
        t0 = time.perf_counter()
        compiled = fn.lower(*a).compile()
        m = compiled.memory_analysis()
        worst = max(worst, m.temp_size_in_bytes)
        out["programs"][prog] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument": m.argument_size_in_bytes,
            "output": m.output_size_in_bytes,
            "alias": m.alias_size_in_bytes,
            "temp": m.temp_size_in_bytes,
            "mosaic": "tpu_custom_call" in compiled.as_text()}
        print(cell.name, prog, json.dumps(out["programs"][prog]), flush=True)
    # per chip: weights + pool + the largest program's temporaries
    out["largest_program_temp"] = worst
    out["n_blocks_that_fit"] = int((HBM - MARGIN - weights - worst)
                                   // block)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="pool to compile with (default: each cell's)")
    ap.add_argument("--only", nargs="*", default=None,
                    help="compile only these programs")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    import repro.kernels.flash_prefill.ops as fp_ops
    import repro.kernels.tree_attention.ops as ta_ops
    import repro.kernels.tree_attention.paged as ta_paged
    for mod in (fp_ops, ta_ops, ta_paged):
        mod.default_interpret = lambda: False   # compile the Mosaic kernels

    for name in args.workload:
        out = rehearse(name, args.n_blocks, args.only)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
