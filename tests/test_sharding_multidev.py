"""Distribution correctness on 8 virtual devices (subprocess so the main
test session keeps 1 device): flash-decode == dense, MoE EP == ref,
elastic checkpoint resharding, and the logical-axis rule translation."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.sharding import DEFAULT_RULES, logical_spec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_logical_spec_divisibility_fallback():
    import jax
    # no mesh active → constrain is a no-op, spec helper still pure
    spec = logical_spec(("batch", None), shape=(7, 3), mesh=None,
                        rules=DEFAULT_RULES)
    assert tuple(spec) == (None, None)


@pytest.mark.slow
def test_flash_decode_equals_dense_8dev():
    _run_subprocess("""
        import jax, numpy as np, dataclasses
        import jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.models import transformer as tx
        from repro.distributed.sharding import sharding_ctx
        cfg = tx.TransformerConfig(n_layers=2, d_model=64, n_heads=8,
                                   n_kv_heads=4, d_ff=128, vocab_size=97,
                                   max_seq_len=64)
        params = tx.init_params(cfg, jax.random.key(0))
        B, T = 2, 5
        rng = np.random.RandomState(0)
        lens = jnp.array([10, 7], dtype=jnp.int32)
        kf = rng.randn(2, B, 64, 4, 8).astype(np.float32) * 0.1
        cache = {"k": jnp.asarray(kf), "v": jnp.asarray(kf) * 0.5}
        toks = jnp.asarray(rng.randint(1, 97, (B, T)), jnp.int32)
        depth = jnp.asarray([[0, 1, 1, 2, 2]] * B, jnp.int32)
        pos = lens[:, None] + depth
        parent = [-1, 0, 0, 1, 2]
        m = np.zeros((T, T), bool)
        for i in range(T):
            j = i
            while j >= 0:
                m[i, j] = True; j = parent[j]
        mask = jnp.asarray(np.stack([m] * B))
        c1, l1 = tx.tree_step(cfg, params, dict(cache), lens, toks, pos, mask)
        cfg2 = dataclasses.replace(cfg, decode_backend="flash_decode")
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        with sharding_ctx(mesh):
            fn = jax.jit(lambda c, le, t, p, mm:
                         tx.tree_step(cfg2, params, c, le, t, p, mm))
            c2, l2 = fn(dict(cache), lens, toks, pos, mask)
        assert np.allclose(np.asarray(l1), np.asarray(l2), atol=3e-5)
        assert np.allclose(np.asarray(c1["k"]), np.asarray(c2["k"]), atol=3e-5)
        assert np.allclose(np.asarray(c1["v"]), np.asarray(c2["v"]), atol=3e-5)
        print("OK")
    """)


@pytest.mark.slow
def test_moe_ep_equals_ref_8dev():
    _run_subprocess("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.models import moe as M
        rng = np.random.RandomState(0)
        N, D, E, F, k = 96, 16, 8, 24, 2
        x = jnp.asarray(rng.randn(N, D).astype(np.float32))
        wr = jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.3)
        wg = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.2)
        wu = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.2)
        wd = jnp.asarray(rng.randn(E, F, D).astype(np.float32) * 0.2)
        ref = M.moe_ref(x, wr, wg, wu, wd, k)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ep = M.moe_ep(x, wr, wg, wu, wd, k, capacity_factor=8.0, mesh=mesh)
        assert np.allclose(np.asarray(ref), np.asarray(ep), atol=1e-4)
        # gradients flow through the EP path (all_to_all transposes)
        g = jax.grad(lambda w: M.moe_ep(x, wr, w, wu, wd, k, 8.0,
                                        mesh).sum())(wg)
        assert np.isfinite(np.asarray(g)).all()
        print("OK")
    """)


@pytest.mark.slow
def test_elastic_checkpoint_reshard_8dev():
    _run_subprocess("""
        import jax, numpy as np, tempfile
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training.checkpoint import CheckpointManager
        mesh8 = jax.make_mesh((4, 2), ("data", "model"))
        x = jnp.arange(64.0).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh8, P("data", "model")))
        with tempfile.TemporaryDirectory() as d:
            m = CheckpointManager(d)
            m.save(1, {"w": xs}, logical_axes={"w": ("batch", "tensor")})
            # restore onto a DIFFERENT mesh shape (elastic: lost 4 devices)
            mesh4 = jax.make_mesh((2, 2), ("data", "model"),
                                  devices=jax.devices()[:4])
            out, step = m.restore({"w": x}, mesh=mesh4)
            assert step == 1
            np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x))
            shard_shape = out["w"].sharding.shard_shape(out["w"].shape)
            assert shard_shape == (4, 4), shard_shape
        print("OK")
    """)


def test_replicas_pinned_to_devices_8dev():
    """In-process replicas pinned to devices 2 and 5 keep their weights,
    caches and steps there, and serve the single engine's tokens."""
    _run_subprocess("""
        import jax, numpy as np
        from repro.fleet import EngineReplica, FleetRouter
        from repro.models.transformer import TransformerConfig, init_params
        from repro.serving.api import EngineConfig, build_engine
        cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=4,
                                n_kv_heads=2, d_ff=64, vocab_size=53,
                                max_seq_len=96)
        ecfg = EngineConfig(lanes=2, prefill_len=16, decoding_length=4,
                            branch_length=3)
        params = init_params(cfg, jax.random.key(3))
        devs = [jax.devices()[2], jax.devices()[5]]
        reps = [EngineReplica(lambda d=d: build_engine(
                    ecfg, cfg, jax.device_put(params, d)),
                    replica_id=f"r{i}", device=d)
                for i, d in enumerate(devs)]
        router = FleetRouter(reps, policy="round_robin")
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 53, size=5 + i).tolist() for i in range(6)]
        for p in prompts:
            router.submit(p)
        router.drain()
        single = build_engine(ecfg, cfg, params)
        ref = [single.submit(p) for p in prompts]
        single.run()
        assert [r["tokens"] for r in router.results()] == \
            [h.result().tokens for h in ref]
        for rep, d in zip(reps, devs):
            cache = rep.engine.scheduler.cache
            assert {x for leaf in jax.tree.leaves(cache)
                    for x in leaf.devices()} == {d}
            assert rep.engine.scheduler.stats.finished == 3
        print("OK")
    """)


@pytest.mark.slow
def test_mesh_factory_shapes():
    _run_subprocess("""
        import jax
        from repro.launch.mesh import make_host_mesh
        m = make_host_mesh(data=4, model=2)
        assert dict(m.shape) == {"data": 4, "model": 2}
        print("OK")
    """)
