"""Serve qwen2-1.5b at its full published width on a TPU, end to end.

    python chip_smoke.py                 # one chip: phases A and B
    python chip_smoke.py --four-chips    # four chips: the replica fleet only

Drives the normal entry points — ``configs.get_arch(...).full_config()``,
``init_params``, ``EngineConfig``, ``build_engine``, ``submit``,
``result`` — with random bfloat16 weights made from ``--seed`` (28 layers,
d_model 1536, 12 query / 2 KV heads, vocab 151936).

  * Phase A: dense KV cache, dense attention backend.
  * Phase B: paged KV cache, Pallas kernels, prefix cache, overlapped
    drafting, ``trie`` + ``prompt_copy`` draft sources.  The compiled fused
    step must hold a Mosaic kernel (``tpu_custom_call``).

Each phase serves 12 requests of mixed prompt lengths, greedy and sampled,
through 4 lanes (so admission happens mid-flight); every request's tokens
must equal ``reference_decode`` through the same StepFns, bit for bit.  The
reference runs the served engine's own executables (``lanes=``: root-only
trees at the full width) and admits each prompt the way it was served
(``like=``: first-cohort, slot or prefix-hit suffix prefill): on the chip a
program of another shape rounds differently in bf16.

``--four-chips`` runs only the fleet: 4 in-process ``EngineReplica``s, one
pinned to each chip, behind ``FleetRouter``.  Each replica's cache must live
on its own chip, and its outputs must equal a single engine on chip 0 that
serves the same requests in the same order.

The script refuses to run without a TPU (no CPU fallback), raises on any
failed check, and prints as its last line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core.draft_sources import DraftPolicy  # noqa: E402
from repro.core.engine import reference_decode  # noqa: E402
from repro.core.request import SamplingParams  # noqa: E402
from repro.fleet import EngineReplica, FleetRouter  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.transformer import init_params  # noqa: E402
from repro.serving.api import (EngineConfig, ServingEngine,  # noqa: E402
                               build_engine, build_session_fns)

ARCH = "qwen2-1.5b"
LANES = 4
PREFILL_LEN = 128
PROMPT_LENS = (3, 9, 17, 33, 65, 100, 128)
MAX_NEW = (24, 40, 16, 48)

DENSE = EngineConfig(lanes=LANES, prefill_len=PREFILL_LEN, backend="dense")
PAGED = EngineConfig(
    lanes=LANES, prefill_len=PREFILL_LEN, backend="pallas",
    kv_layout="paged", block_size=64, prefix_cache=True, overlap_drafts=True,
    draft_policy=DraftPolicy(sources=("trie", "prompt_copy")))


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def make_requests(vocab: int, n: int, seed: int):
    """n (prompt, SamplingParams) pairs: mixed prompt lengths, every third
    prompt opening with one shared 72-token head (prefix-cache hits past
    the first 64-token block), odd requests sampled at varied temperatures,
    even ones greedy."""
    rng = np.random.RandomState(seed)
    head = rng.randint(1, vocab, size=72).tolist()
    reqs = []
    for i in range(n):
        if i % 3 == 0:
            tail = rng.randint(1, vocab, size=8 + 8 * (i % 5)).tolist()
            prompt = (head + tail)[:PREFILL_LEN]
        else:
            prompt = rng.randint(
                1, vocab, size=PROMPT_LENS[i % len(PROMPT_LENS)]).tolist()
        max_new = MAX_NEW[i % len(MAX_NEW)]
        if i % 2:
            params = SamplingParams(max_new_tokens=max_new, sample=True,
                                    temperature=(0.7, 1.0, 1.3)[i % 3],
                                    seed=100 + i)
        else:
            params = SamplingParams(max_new_tokens=max_new)
        reqs.append((prompt, params))
    return reqs


class CompileClock:
    """Sums XLA backend compile time and counts compiles (JAX monitoring
    events), so each phase can report its cold-compile seconds."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def mark(self):
        return self.seconds, self.count


def run_phase(name: str, model_cfg, params, ecfg: EngineConfig, reqs,
              clock: CompileClock):
    """Serve ``reqs`` through one engine, then hold every request to
    ``reference_decode`` through the same StepFns.  Returns the engine."""
    c0, n0 = clock.mark()
    t0 = time.perf_counter()
    engine = build_engine(ecfg, model_cfg, params)
    handles = [engine.submit(prompt, params=sp) for prompt, sp in reqs]
    results = [h.result() for h in handles]
    serve_s = time.perf_counter() - t0
    st = engine.stats
    check(all(r.finish_reason == "length" for r in results),
          f"{name}: unfinished requests "
          f"{[r.finish_reason for r in results]}")
    check(st.admitted == len(reqs) and st.admitted > ecfg.lanes,
          f"{name}: admitted {st.admitted} of {len(reqs)}")
    tokens = sum(len(r.tokens) for r in results)
    print(f"{name}: served {len(reqs)} requests / {tokens} tokens in "
          f"{serve_s:.1f} s (compile included); {st.decode_steps} decode "
          f"steps, {st.active_lane_steps} lane-steps, "
          f"{(tokens - len(reqs)) / max(st.active_lane_steps, 1):.2f} "
          f"accepted tokens per lane-step, occupancy {st.occupancy:.2f}",
          flush=True)
    if ecfg.prefix_cache:
        print(f"{name}: prefix cache {st.prefix_hits}/{st.prefix_lookups} "
              f"hits, {st.prefix_hit_tokens} prompt tokens skipped",
              flush=True)
    t1 = time.perf_counter()
    for i, ((prompt, sp), res) in enumerate(zip(reqs, results)):
        ref = reference_decode(engine.fns, prompt, params=sp,
                               eos_id=ecfg.eos_id, pad_id=ecfg.pad_id,
                               lanes=ecfg.lanes, like=res.stats)
        check(res.tokens == ref,
              f"{name}: request {i} differs from reference_decode "
              f"(served {res.tokens[:8]}..., reference {ref[:8]}...)")
    c1, n1 = clock.mark()
    print(f"{name}: {len(reqs)}/{len(reqs)} requests equal reference_decode "
          f"({time.perf_counter() - t1:.1f} s); {n1 - n0} compiles, "
          f"{c1 - c0:.1f} s compiling", flush=True)
    return engine


def fused_step_hlo(engine, params) -> str:
    """Compiled text of the engine's fused step at the shapes it served."""
    sch = engine.scheduler
    B, W = sch.lanes, sch.width
    z = np.zeros((B, W), np.int32)
    lowered = engine.fns.fused_step._jitted.lower(
        params, sch.cache, sch.lens, z, z, np.zeros((B, W, W), bool), z,
        np.zeros((B,), np.int32), sch._lane_params_all())
    return lowered.compile().as_text()


def run_fleet(model_cfg, params, ecfg: EngineConfig, reqs, devices,
              clock: CompileClock) -> None:
    """One in-process replica per device behind the router (drained in
    threads, so their compiles overlap).  Then a single engine on
    devices[0] serves each replica's requests again, in the same order from
    a fresh scheduler — the same admissions through the same programs — and
    must give the same tokens."""
    c0, n0 = clock.mark()
    t0 = time.perf_counter()

    def build_on(device):
        return build_engine(ecfg, model_cfg, jax.device_put(params, device))

    replicas = [EngineReplica(lambda d=d: build_on(d), replica_id=f"r{i}",
                              device=d) for i, d in enumerate(devices)]
    router = FleetRouter(replicas, policy="round_robin")
    placements = [router.submit(prompt, sp) for prompt, sp in reqs]
    with ThreadPoolExecutor(len(replicas)) as pool:
        for job in [pool.submit(rep.drain) for rep in replicas]:
            job.result()
    results = router.results()
    serve_s = time.perf_counter() - t0

    homes = []
    for rep, dev in zip(replicas, devices):
        sch = rep.engine.scheduler
        where = {d for leaf in jax.tree.leaves(sch.cache)
                 for d in leaf.devices()}
        check(where == {dev}, f"replica {rep.replica_id}: cache on {where}, "
              f"expected {dev}")
        homes.append(dev.id)
        print(f"fleet: replica {rep.replica_id} on device {dev.id} served "
              f"{sch.stats.finished} requests in {sch.stats.decode_steps} "
              "decode steps", flush=True)
    check(len(set(homes)) == len(devices), f"replicas share devices {homes}")

    fns = build_session_fns(ecfg, model_cfg, params)
    for r, rep in enumerate(replicas):
        mine = [p.index for p in placements if p.replica == r]
        single = ServingEngine(fns, ecfg)
        handles = [single.submit(reqs[i][0], params=reqs[i][1]) for i in mine]
        single.run()
        bad = [i for i, h in zip(mine, handles)
               if h.result().tokens != results[i]["tokens"]]
        check(not bad, f"replica {rep.replica_id}: requests {bad} differ "
              f"from the single engine on device {devices[0].id}")
    tokens = sum(len(r["tokens"]) for r in results)
    c1, n1 = clock.mark()
    print(f"fleet: {len(reqs)}/{len(reqs)} requests ({tokens} tokens) equal "
          f"the single engine; fleet served in {serve_s:.1f} s wall, "
          f"{n1 - n0} compiles, {c1 - c0:.1f} s compiling", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica fleet path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache_dir}", flush=True)

    model_cfg = dataclasses.replace(get_arch(ARCH).full_config(),
                                    dtype="bfloat16", param_dtype="bfloat16")
    t0 = time.perf_counter()
    # one compiled program draws every weight (op-by-op it took 72 s)
    params = jax.block_until_ready(jax.jit(
        lambda key: init_params(model_cfg, key))(jax.random.key(args.seed)))
    print(f"{ARCH}: {model_cfg.n_params() / 1e9:.2f} B parameters in "
          f"bfloat16, initialised in {time.perf_counter() - t0:.1f} s",
          flush=True)

    if args.four_chips:
        reqs = make_requests(model_cfg.vocab_size, 24, args.seed + 2)
        run_fleet(model_cfg, params, PAGED, reqs, devices[:4], clock)
    else:
        reqs = make_requests(model_cfg.vocab_size, 12, args.seed)
        run_phase("phase A (dense KV, dense attention)", model_cfg, params,
                  DENSE, reqs, clock)
        reqs = make_requests(model_cfg.vocab_size, 12, args.seed + 1)
        engine = run_phase("phase B (paged KV, Pallas, prefix cache, "
                           "overlap)", model_cfg, params, PAGED, reqs, clock)
        check(engine.stats.prefix_hits > 0, "phase B: no prefix-cache hit")
        check("tpu_custom_call" in fused_step_hlo(engine, params),
              "phase B: compiled fused step holds no Mosaic kernel")
        print("phase B: compiled fused step holds a Mosaic kernel "
              "(tpu_custom_call)", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
