"""The analysis layer itself (ISSUE 9): linter rules R1-R5 against
known-bad and known-good fixture snippets, sanitizer units (double free,
leak-at-idle, out-of-order lifecycle, poison probes, retrace manifest),
and the serving-level integration — a sanitized scheduler run stays
bit-identical, and the lifecycle machine pins PR 8's cancel-of-pending
ordering (blocks held until the deferred drain).
"""
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import pytest

from repro.analysis.lint import lint_file, lint_source, main as lint_main
from repro.analysis.sanitizer import (ADMITTED, DRAINED, InvariantViolation,
                                      LifecycleMonitor, RetraceMonitor,
                                      ShadowLedger)
from repro.serving.block_allocator import BlockAllocator

pytestmark = pytest.mark.analysis

REPO = Path(__file__).resolve().parents[1]


def _rules(source, rule_id):
    """Finding rule-ids of one snippet, filtered to one rule."""
    return [f for f in lint_source(textwrap.dedent(source))
            if f.rule == rule_id]


# ------------------------------------------------------------------- R1
R1_BAD = """
    import numpy as np

    class Sched:
        def _pull(self, x):
            return np.asarray(x)

        def step(self):
            cache, chosen = self.fns.fused_step(self.cache, self.lens)
            n = int(chosen[0])
            arr = np.asarray(chosen)
            chosen.block_until_ready()
            return n, arr, chosen.item()
"""

R1_GOOD = """
    import numpy as np

    class Sched:
        def _pull(self, x):
            return np.asarray(x)

        def step(self):
            cache, chosen = self.fns.fused_step(self.cache, self.lens)
            chosen = self._pull(chosen)
            toks = np.asarray(self.prompt, dtype=np.int32)
            return int(chosen[0]), toks
"""


def test_r1_flags_raw_pulls_on_device_values():
    found = _rules(R1_BAD, "R1")
    assert len(found) == 4          # int(), np.asarray(), buR(), .item()
    assert any("block_until_ready" in f.message for f in found)


def test_r1_accepts_pull_choke_point_and_host_values():
    # laundering through _pull() makes the name host data again, and
    # np.asarray on plain host values (the prompt list) is fine
    assert _rules(R1_GOOD, "R1") == []


def test_r1_ignores_classes_without_pull_contract():
    src = """
        import numpy as np

        class NotAScheduler:
            def step(self):
                out = self.fns.fused_step(self.cache)
                return int(out[0])
    """
    assert _rules(src, "R1") == []


def test_r1_suppression_comment():
    src = """
        import numpy as np

        class Sched:
            def _pull(self, x):
                return np.asarray(x)

            def warmup(self):
                c, chosen = self.fns.prefill(self.toks, self.lens)
                return int(chosen[0])  # repro-lint: disable=R1
    """
    assert _rules(src, "R1") == []


# ------------------------------------------------------------------- R2
def test_r2_flags_bare_jit_and_missing_argnums():
    src = """
        import jax

        @jax.jit
        def f(x):
            return x

        g = jax.jit(lambda x: x)
    """
    assert len(_rules(src, "R2")) == 2


def test_r2_flags_self_closure():
    src = """
        import functools, jax

        class Sched:
            def make(self):
                @functools.partial(jax.jit, donate_argnums=())
                def f(x):
                    return x + self.offset
                return f
    """
    found = _rules(src, "R2")
    assert len(found) == 1 and "closes over" in found[0].message


def test_r2_accepts_explicit_argnums():
    src = """
        import functools, jax

        @functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2,))
        def f(cache, x, n):
            return cache, x

        g = jax.jit(lambda x: x, donate_argnums=())
    """
    assert _rules(src, "R2") == []


# ------------------------------------------------------------------- R3
def test_r3_flags_pr8_cancel_shape_dropped_free_result():
    # the PR 8 use-after-free reconstruction: cancel-of-pending frees the
    # request's blocks mid-dispatch and throws away the refcount-zero ids
    src = """
        class Sched:
            def cancel_pending(self, rid, lane):
                del self._pending[lane]
                self.alloc.free(rid)
    """
    found = _rules(src, "R3")
    assert len(found) == 1 and "dropped on the floor" in found[0].message


def test_r3_flags_unpaired_acquire():
    src = """
        class PrefixAdopter:
            def adopt(self, rid, blocks):
                self.alloc.share(rid, blocks)
    """
    found = _rules(src, "R3")
    assert len(found) == 1 and "share" in found[0].message


def test_r3_accepts_paired_and_consumed():
    src = """
        class Sched:
            def admit(self, rid, blocks):
                self.alloc.share(rid, blocks)

            def retire(self, rid):
                freed = self.alloc.free(rid)
                self.scrub(freed)

        class BlockAllocator:
            def free(self, rid):
                return []

            def share(self, rid, blocks):
                self.noop(blocks)
    """
    # the scheduler pairs + consumes; the allocator DEFINES the API and
    # is skipped entirely
    assert _rules(src, "R3") == []


# ------------------------------------------------------------------- R4
def test_r4_flags_value_dependent_shapes_into_jitted_fns():
    src = """
        import jax
        import numpy as np

        step = jax.jit(lambda t: t, donate_argnums=())

        def go(toks, n):
            a = step(np.asarray(toks[:n]))
            b = step(np.zeros((len(toks),)))
            return a, b
    """
    assert len(_rules(src, "R4")) == 2


def test_r4_accepts_fixed_buckets():
    src = """
        import jax
        import numpy as np

        step = jax.jit(lambda t: t, donate_argnums=())

        def go(toks, n, buf):
            buf[0, :n] = np.asarray(toks[:n])   # host staging: fine
            return step(buf)
    """
    assert _rules(src, "R4") == []


# ------------------------------------------------------------------- R5
def test_r5_flags_donation_mask_mutations():
    src = """
        import numpy as np

        def sync(cache, tables):
            cache["block_tables"] = np.asarray(tables)
            del cache["k"]
            cache.pop("v")
    """
    assert len(_rules(src, "R5")) == 3


def test_r5_accepts_device_leaves():
    src = """
        import jax.numpy as jnp

        def sync(cache, tables):
            cache["block_tables"] = jnp.asarray(tables)
            other = {}
            other["x"] = np.asarray([1])
    """
    assert _rules(src, "R5") == []


# ------------------------------------------------------------------- R6
def test_r6_flags_unpaired_state_dict():
    src = """
        class SaveOnly:
            def state_dict(self):
                return {}

        class LoadOnly:
            def load_state_dict(self, state):
                pass
    """
    found = _rules(src, "R6")
    assert len(found) == 2
    assert "never be restored" in found[0].message
    assert "never donates" in found[1].message


def test_r6_accepts_paired_and_suppressed():
    src = """
        class Paired:
            def state_dict(self):
                return {}

            def load_state_dict(self, state):
                pass

        class Justified:
            def load_state_dict(self, state):  # repro-lint: disable=R6
                pass
    """
    assert _rules(src, "R6") == []


def test_r6_inherited_half_does_not_pair():
    # Inheriting one half does not satisfy the pairing: the serialized
    # shape is the defining class's business, so a subclass overriding
    # only load_state_dict is flagged.
    src = """
        class Base:
            def state_dict(self):
                return {}

            def load_state_dict(self, state):
                pass

        class Child(Base):
            def load_state_dict(self, state):
                pass
    """
    found = _rules(src, "R6")
    assert len(found) == 1
    assert "Child" in found[0].message


# ------------------------------------------------------- driver / repo gate
def test_repo_lints_clean():
    """The merge gate: `python -m repro.analysis.lint src/` exits 0."""
    assert lint_main([str(REPO / "src")]) == 0


def test_lint_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x\n")
    env_cmd = [sys.executable, "-m", "repro.analysis.lint", str(bad)]
    proc = subprocess.run(env_cmd, capture_output=True, text=True,
                          cwd=str(REPO))
    assert proc.returncode == 1
    assert "R2" in proc.stdout
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--list-rules"],
        capture_output=True, text=True, cwd=str(REPO))
    assert proc.returncode == 0
    assert all(r in proc.stdout
               for r in ("R1", "R2", "R3", "R4", "R5", "R6"))


def test_lint_file_select(tmp_path):
    from repro.analysis.rules import all_rules
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\ng = jax.jit(lambda x: x)\n")
    only_r1 = [r for r in all_rules() if r.rule_id == "R1"]
    assert lint_file(bad, only_r1) == []
    assert len(lint_file(bad)) == 1


# ----------------------------------------------------------- lifecycle unit
def test_lifecycle_legal_path():
    mon = LifecycleMonitor()
    for state in ("queued", "admitted", "active", "retiring", "drained"):
        mon.transition(7, state)
    assert mon.state(7) == DRAINED
    mon.assert_all_drained()


def test_lifecycle_out_of_order_raises_with_history():
    mon = LifecycleMonitor()
    mon.transition(3, "queued")
    mon.transition(3, "admitted")
    with pytest.raises(InvariantViolation) as exc:
        mon.transition(3, "drained")    # skipped retiring
    assert "queued -> admitted" in str(exc.value)
    assert mon.state(3) == ADMITTED     # rejected transition did not apply


def test_lifecycle_stuck_request_fails_idle_audit():
    mon = LifecycleMonitor()
    mon.transition(1, "queued")
    mon.transition(1, "admitted")
    with pytest.raises(InvariantViolation, match="not drained"):
        mon.assert_all_drained()


# -------------------------------------------------------------- ledger unit
def _allocated_pair():
    alloc = BlockAllocator(8, 4)
    ledger = ShadowLedger()
    alloc.observer = ledger
    return alloc, ledger


def test_ledger_mirrors_clean_lifecycle():
    alloc, ledger = _allocated_pair()
    alloc.alloc(0, 2, reserve=3)
    alloc.extend(0, 1)
    alloc.free(0)
    ledger.assert_idle(alloc)


def test_ledger_double_free():
    alloc, ledger = _allocated_pair()
    blocks = alloc.alloc(0, 2)
    alloc.free(0)
    with pytest.raises(InvariantViolation, match="double free"):
        ledger.on_event("free_enter", rid=0, table=blocks)


def test_ledger_leak_at_idle():
    alloc, ledger = _allocated_pair()
    alloc.alloc(0, 2)
    with pytest.raises(InvariantViolation, match="leak|allocations"):
        ledger.assert_idle(alloc)


def test_ledger_free_while_request_active():
    # PR 8's use-after-free window: blocks freed while the request's
    # dispatch may still be writing into them (lifecycle not retiring)
    lifecycle = LifecycleMonitor()
    alloc = BlockAllocator(8, 4)
    ledger = ShadowLedger(lifecycle)
    alloc.observer = ledger
    lifecycle.transition(5, "queued")
    alloc.alloc(5, 2)
    lifecycle.transition(5, "admitted")
    with pytest.raises(InvariantViolation, match="use-after-free"):
        alloc.free(5)       # never transitioned to retiring


def test_ledger_cache_ref_pairing():
    alloc, ledger = _allocated_pair()
    blocks = alloc.alloc(0, 2)
    alloc.cache_ref(blocks)
    assert alloc.free(0) == []          # cache still holds both
    assert sorted(alloc.cache_unref(blocks)) == sorted(blocks)
    ledger.assert_idle(alloc)


def test_ledger_poison_probe():
    import numpy as np
    ledger = ShadowLedger()
    # the paged pool's layout: (L, n_blocks, block_size, K*dh_p)
    cache = {"k": np.zeros((2, 8, 4, 256)), "v": np.zeros((2, 8, 4, 256))}
    ledger.on_scrubbed([3])
    ledger.check_poison(cache)          # all-zero: clean
    cache["k"][0, 3, 1] = 1.0           # stray write into freed block
    with pytest.raises(InvariantViolation, match="use-after-free write"):
        ledger.check_poison(cache)


# ------------------------------------------------------------- retrace unit
def _fake_fns(counts):
    def member(name):
        fn = lambda *a, **k: None                      # noqa: E731
        fn._cache_size = lambda: counts[name]
        return fn
    return types.SimpleNamespace(
        prefill=member("prefill"), fused_step=member("fused_step"),
        suffix_buckets=())


def test_retrace_monitor_deltas():
    counts = {"prefill": 1, "fused_step": 1}
    mon = RetraceMonitor(_fake_fns(counts))
    mon.check()                         # no compiles since attach
    counts["fused_step"] += 1           # one compile: within manifest
    mon.check()
    counts["fused_step"] += 1           # second compile: retrace
    with pytest.raises(InvariantViolation, match="retrace"):
        mon.check()


def test_retrace_manifest_override():
    counts = {"prefill": 0, "fused_step": 0}
    mon = RetraceMonitor(_fake_fns(counts), manifest={"prefill": 3})
    counts["prefill"] = 3
    mon.check()
    counts["prefill"] = 4
    with pytest.raises(InvariantViolation):
        mon.check()


# ------------------------------------------------- serving-level integration
@pytest.fixture(scope="module")
def paged_fns():
    import jax
    from repro.models.transformer import TransformerConfig, init_params
    from repro.serving.session import make_session_fns
    cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
                            d_ff=64, vocab_size=53, max_seq_len=160)
    params = init_params(cfg, jax.random.key(11))
    return make_session_fns(cfg, params, slots=9, prefill_len=32,
                            kv_layout="paged", block_size=8)


def _mk_sched(fns, **kw):
    from repro.core import LookaheadConfig
    from repro.serving.scheduler import ContinuousScheduler
    la = LookaheadConfig(decoding_length=8, branch_length=4)
    return ContinuousScheduler(fns, la, lanes=2, prefill_len=32, **kw)


def _prompts(n, seed):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 52, size=rng.randint(4, 26)).tolist()
            for _ in range(n)]


def test_sanitized_run_bit_identical_and_audited(paged_fns):
    """sanitize=True changes nothing about outputs, and a full run ends
    with the idle audit (lifecycles drained, ledger matched, retrace
    manifest honored) having passed."""
    prompts = _prompts(5, seed=21)
    outs = {}
    for sanitize in (False, True):
        sched = _mk_sched(paged_fns, sanitize=sanitize, scrub_freed=True,
                          overlap_drafts=True, prefix_cache=True)
        rids = [sched.submit(p, 12) for p in prompts]
        sched.run()
        outs[sanitize] = [sched.results[r].tokens for r in rids]
    assert outs[True] == outs[False]


def test_sanitizer_default_off_not_even_imported(paged_fns):
    sched = _mk_sched(paged_fns)
    assert sched.sanitizer is None
    assert sched.allocator.observer is None


def test_cancel_of_pending_holds_blocks_until_deferred_drain(paged_fns):
    """Regression pin for PR 8's cancel use-after-free fix, via the
    lifecycle machine: cancelling an overlap admission whose prefill is
    still in flight must leave the request in `retiring` WITH its blocks
    still owned (nothing may re-allocate them under the in-flight
    dispatch); the deferred drain then frees the blocks and moves it to
    `drained`."""
    prompts = _prompts(3, seed=22)
    sched = _mk_sched(paged_fns, sanitize=True, scrub_freed=True,
                      overlap_drafts=True)
    r0 = sched.submit(prompts[0], 12)
    sched.step()                         # initial cohort: r0 active
    r1 = sched.submit(prompts[1], 12)
    sched._admit()                       # overlap: r1's prefill in flight
    assert 1 in sched._pending and sched._pending[1].rid == r1
    assert sched.cancel(r1)
    san = sched.sanitizer
    # the fix under test: retiring (blocks HELD), not drained (blocks freed)
    assert san.lifecycle.state(r1) == "retiring"
    assert sched.allocator.owns(r1)
    assert r1 in sched.results and sched.results[r1].cancelled
    sched.run()                          # deferred drain runs + idle audit
    assert san.lifecycle.history(r1) == ["queued", "admitted", "retiring",
                                         "drained"]
    assert not sched.allocator.owns(r1)
    assert sched.results[r0].tokens      # survivor unharmed


def test_premature_free_of_pending_raises(paged_fns):
    """The sanitizer actually catches the PR 8 bug shape: freeing a
    pending admission's blocks at cancel time (instead of deferring to
    the drain) trips the ledger's use-after-free gate."""
    prompts = _prompts(2, seed=23)
    sched = _mk_sched(paged_fns, sanitize=True, scrub_freed=True,
                      overlap_drafts=True)
    sched.submit(prompts[0], 12)
    sched.step()
    r1 = sched.submit(prompts[1], 12)
    sched._admit()
    assert sched._pending[1].rid == r1   # prefill in flight
    with pytest.raises(InvariantViolation, match="use-after-free"):
        sched.allocator.free(r1)         # the buggy pre-PR-8 teardown
