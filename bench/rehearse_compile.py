"""Compile a cell's serving programs for a described TPU v5e, on the CPU.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py --workload <name> \
        [--n-blocks N]

Lowers and compiles, at the cell's real shapes, every program its window
can run (first-cohort prefill, slot prefill, the largest suffix bucket,
the fused tree-verify step) for one chip of a described ``v5e:2x2``
topology, with the Pallas kernels compiled (not interpreted).  It prints
each program's ``memory_analysis()`` and the paged pool that fits in the
chip's HBM beside the weights, the copy of the pool that the layer-scanning
steps hold, and the largest program's other buffers.
Nothing runs, so nothing here is a time or a chip measurement.
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="pool to compile with (default: the cell's)")
    ap.add_argument("--only", nargs="*", default=None,
                    help="compile only these programs")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import repro.kernels.flash_prefill.ops as fp_ops
    import repro.kernels.tree_attention.ops as ta_ops
    import repro.kernels.tree_attention.paged as ta_paged
    for mod in (fp_ops, ta_ops, ta_paged):
        mod.default_interpret = lambda: False   # compile the Mosaic kernels

    from harness.main import engine_config
    from harness.model import (Guide, bias_size, make_bias, make_weights,
                               program_config, shape_of)
    from harness.spec import load_cell
    from repro.serving.api import build_session_fns

    cell = load_cell(args.workload)
    s = dict(cell.settings)
    if args.n_blocks:
        s["n_blocks"] = args.n_blocks
    shape = shape_of(cell.config)
    ecfg = engine_config(s)
    tcfg = program_config(shape, context=shape["context"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(lambda: make_weights(shape, 0)))
    bias = None
    if cell.traffic.get("guide"):
        g = cell.traffic["guide"]
        bias = make_bias(Guide.of(g, shape["vocab"]),
                         bias_size(shape, float(g["bias_sigmas"])))
    fns = build_session_fns(ecfg, tcfg, params, logits_transform=bias)
    B, W, P = ecfg.lanes, ecfg.slots, ecfg.prefill_len
    bpl = -(-shape["context"] // ecfg.block_size)
    L, K, dh = shape["layers"], shape["kv_heads"], shape["head_dim"]
    nb = int(s["n_blocks"])
    i32, bf = jnp.int32, jnp.bfloat16

    def sd(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one)

    def lanes(n):
        return {"greedy": sd((n,), jnp.bool_), "temp": sd((n,), jnp.float32),
                "seed": sd((n,), jnp.uint32)}

    cache = {"k": sd((L, nb, ecfg.block_size, K, dh), bf),
             "v": sd((L, nb, ecfg.block_size, K, dh), bf),
             "block_tables": sd((B, bpl), i32)}
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
    block = 2 * L * ecfg.block_size * K * dh * 2
    pool = nb * block
    out = {"workload": cell.name, "weights_bytes": weights,
           "block_bytes": block, "n_blocks": nb, "pool_bytes": pool,
           "programs": {}}
    worst = 0
    for name, (fn, a) in progs.items():
        if args.only and name.split("[")[0] not in args.only:
            continue
        t0 = time.perf_counter()
        compiled = fn.lower(*a).compile()
        m = compiled.memory_analysis()
        # the steps that scan the layers over the pool hold a second copy
        # of it among their temporaries; what is left beyond that copy
        copies = name.startswith(("fused_step", "prefill_suffix"))
        extra = m.temp_size_in_bytes - (pool if copies else 0)
        worst = max(worst, extra)
        out["programs"][name] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument": m.argument_size_in_bytes,
            "output": m.output_size_in_bytes,
            "alias": m.alias_size_in_bytes,
            "temp": m.temp_size_in_bytes,
            "temp_beyond_pool_copy": extra,
            "mosaic": "tpu_custom_call" in compiled.as_text()}
        print(name, json.dumps(out["programs"][name]), flush=True)
    # weights + pool + its copy in the step + the largest other buffers
    fit = (HBM - MARGIN - weights - worst) // (2 * block)
    out["largest_program_buffers"] = worst
    out["n_blocks_that_fit"] = int(fit)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
