"""``correct`` comes out false when the timed path is broken underneath.

Each test drives a whole run of the rag cell at its CPU rehearsal size
(the chip check skipped, everything else as on the chip: set-up, warm-up,
the open-loop window, the drain, the reference) with one fault planted in
the program's step, and reads the verdict.  The control test reads the
lower-precision reference at the same size.
"""
import time

import pytest

from harness import main
from harness.main import CompileClock, run_once
from harness.spec import load_cell

CELL = "qwen2-1.5b.rag"


@pytest.fixture(scope="module")
def env():
    import jax
    saved = dict(main.REHEARSAL)
    # more requests compared, so every lane's output is in the sample
    main.REHEARSAL["correct"] = dict(saved["correct"], requests=16)
    cell = load_cell(CELL)
    # load enough to keep every lane of the rehearsal busy
    cell.settings["load"] = dict(cell.settings["load"], rate=6.0)
    yield cell, jax.devices(), CompileClock()
    main.REHEARSAL.clear()
    main.REHEARSAL.update(saved)


def serve(env, seed=21, controls=()):
    cell, devs, clock = env
    return run_once(cell, seed, 4.0, False, devs, clock, time.perf_counter(),
                    rehearse=True, controls=controls)


def test_sound_run_is_correct(env):
    res = serve(env)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


def test_altered_token_fails(env, monkeypatch):
    """A token altered where it is produced: the fused step's choice is
    shifted by one before the accept walk."""
    import jax.numpy as jnp

    import repro.models.transformer as tx
    orig = tx.verify_accept_device

    def bad(tokens, parent, n_live, chosen):
        other = jnp.where(chosen > 2, chosen - 1, chosen + 1)
        return orig(tokens, parent, n_live, other)
    monkeypatch.setattr(tx, "verify_accept_device", bad)
    res = serve(env)
    assert not res["correct"], res["checks"]


def test_step_that_keeps_its_state_fails(env, monkeypatch):
    """A step that returns its state unchanged: the tree forward writes no
    KV, so later tokens attend to stale rows."""
    import repro.models.transformer as tx
    orig = tx.tree_step_paged

    def bad(cfg, params, cache, *a):
        _, logits = orig(cfg, params, cache, *a)
        return cache, logits
    monkeypatch.setattr(tx, "tree_step_paged", bad)
    res = serve(env)
    assert not res["correct"], res["checks"]


def test_half_the_batch_left_out_fails(env, monkeypatch):
    """Half of the lanes left out: the upper half of the batch gets the
    lower half's logits."""
    import jax.numpy as jnp

    import repro.models.transformer as tx
    orig = tx.tree_step_paged

    def bad(cfg, params, cache, *a):
        cache, logits = orig(cfg, params, cache, *a)
        h = logits.shape[0] // 2
        return cache, jnp.concatenate([logits[:h], logits[:h]], axis=0)
    monkeypatch.setattr(tx, "tree_step_paged", bad)
    res = serve(env)
    assert not res["correct"], res["checks"]


def test_lower_precision_control_reads_wider(env):
    """The control: at each position of the served prompts and tokens, the
    first choice of the reference in float8 weights lies further below the
    float32 reference's best than the served tokens do, on three seeds.
    Without the guide bias the model alone picks every token, so near-ties
    (where precision shows) are common at this small size."""
    before = main.REHEARSAL["bias_sigmas"]
    main.REHEARSAL["bias_sigmas"] = 0.0
    try:
        served, ctl = [], []
        for seed in (31, 32, 33):
            res = serve(env, seed=seed, controls=("fp8",))
            served.append(res["checks"]["max_logit_gap"]["value"])
            ctl.append(res["control_gap"]["fp8"])
    finally:
        main.REHEARSAL["bias_sigmas"] = before
    assert max(ctl) > max(served), (served, ctl)
