"""The traffic generator: seeded, stratified, and as its files say."""
import json
import os

import numpy as np
import pytest

from harness.model import Guide, add_bias, guide_walk, make_bias
from harness.traffic import Limits, Mix, quantiles, zipf_ranks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 4000000017          # larger than 32 bits, as the driver's are


def mix_file(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def rag_mix(seed=SEED, vocab=151936):
    t = mix_file("rag")
    guide = Guide.of(t["guide"], vocab)
    return Mix(t, seed, vocab, guide, Limits(1152, 1536, 33)), guide


def test_same_seed_same_requests():
    a, _ = rag_mix()
    b, _ = rag_mix()
    ra, rb = a.open_loop(8.0, 30.0), b.open_loop(8.0, 30.0)
    assert [(r.prompt, r.max_new, r.due) for r in ra] == \
        [(r.prompt, r.max_new, r.due) for r in rb]
    c, _ = rag_mix(seed=SEED + 1)
    assert [r.prompt for r in c.open_loop(8.0, 30.0)] != \
        [r.prompt for r in ra]


def test_every_seed_does_the_same_work():
    """One schedule for every seed: the same sizes, ranks, documents and
    arrivals in the same order; the seed changes the other tokens."""
    a, _ = rag_mix()
    c, _ = rag_mix(seed=7)
    ra, rc = a.open_loop(8.0, 30.0), c.open_loop(8.0, 30.0)
    assert len(ra) == len(rc) == 240
    assert [(r.max_new, r.tag, len(r.prompt), r.due) for r in ra] == \
        [(r.max_new, r.tag, len(r.prompt), r.due) for r in rc]
    assert all(x.prompt != y.prompt for x, y in zip(ra, rc))
    assert all(a.document(int(r.tag[4:])) == c.document(int(r.tag[4:]))
               for r in ra)
    # stratified: the sizes are the quantiles of the file's distribution
    assert sorted(r.max_new for r in ra) == sorted(
        quantiles(mix_file("rag")["output"]["length"], 240).tolist())
    dues = [r.due for r in ra]
    assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 30.0
    # the warm-up's batch has a schedule of its own
    wa, wc = a.requests(32, 30), c.requests(32, 30)
    assert [r.max_new for r in wa] == [r.max_new for r in wc]
    assert [r.max_new for r in wa] != [r.max_new for r in ra[:32]]


def test_lengths_follow_the_file():
    t = mix_file("rag")
    out = quantiles(t["output"]["length"], 4000)
    assert out.min() >= 4 and out.max() <= 256
    # capped lognormal of mean 82: the mean sits just under 82
    assert 75 < out.mean() <= 82
    docs = quantiles(t["documents"]["length"], 2048)
    assert docs.max() <= 1024 and 220 < docs.mean() <= 241
    q = quantiles(t["question"]["length"], 4100)
    assert q.min() == 8 and q.max() == 48


def test_zipf_ranks():
    r = zipf_ranks(2048, 1.0, 10000)
    share = np.bincount(r, minlength=2048) / len(r)
    h = (1.0 / np.arange(1, 2049)).sum()
    assert share[0] == pytest.approx(1 / h, abs=2e-3)
    assert share[1] == pytest.approx(0.5 / h, abs=2e-3)


def test_questions_end_on_a_phase_aligned_span():
    """The guide's successor of a question's last token is the next token
    of its document: the answer continues the document."""
    m, guide = rag_mix()
    sys_len = len(m.system)
    for r in m.open_loop(8.0, 5.0):
        doc = m.document(int(r.tag[4:]))
        assert r.prompt[:sys_len] == m.system
        assert r.prompt[sys_len:sys_len + len(doc)] == doc
        # the document is a walk of the guide at its own positions
        assert doc == guide_walk(guide, doc[0], sys_len, len(doc)).tolist()
        p = len(r.prompt) - 1
        nxt = int(guide.successor(np.int64(r.prompt[-1]), np.int64(p)))
        tail = r.prompt[-8:]
        hits = [i for i in range(len(doc) - 8) if doc[i:i + 8] == tail]
        assert hits, "question does not end on a span of its document"
        # the span sits where the guide's rules are those of its document
        assert any(doc[i + 8] == nxt and (sys_len + i - (p - 7))
                   % guide.period == 0 for i in hits)


def test_free_choice_rejoins_the_walk():
    """At a free position the whole group of the successor gets the bias,
    and whichever member the model picks, the walk goes on as before."""
    _, guide = rag_mix()
    every, group = guide.free_every, guide.group
    assert every and group > 1
    p = 5 * every - 1                        # predicts a free token
    start = 1000
    walk = guide_walk(guide, start, p, 6)
    ids, on = guide.candidates(np.int64(start), np.int64(p))
    picked = ids[on]
    assert len(picked) == group and walk[1] in picked
    assert np.all(picked // group == walk[1] // group)
    for m in picked[[0, 7, -1]]:
        alt = guide_walk(guide, int(m), p + 1, 5)
        assert alt[1:].tolist() == walk[2:].tolist()
    ids, on = guide.candidates(np.int64(start), np.int64(p + 1))
    assert int(on.sum()) == 1


def test_bias_on_device_and_host_agree():
    """The transform served in the program and the host copy the check
    adds to the reference put the same bias on the same logits."""
    import jax.numpy as jnp
    vocab = 4096
    guide = Guide.of(mix_file("rag")["guide"], vocab)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, vocab, size=(3, 70))
    pos = np.arange(70)[None, :] + np.array([[0], [31], [400]])
    lg = rng.standard_normal((3, 70, vocab)).astype(np.float32)
    dev = np.asarray(make_bias(guide, 2.5)(jnp.asarray(lg), jnp.asarray(toks),
                                            jnp.asarray(pos)))
    host = add_bias(lg.reshape(-1, vocab), guide, toks.reshape(-1),
                    pos.reshape(-1), 2.5).reshape(lg.shape)
    np.testing.assert_array_equal(dev, host)
    raised = (dev - lg > 1.0).sum(-1)
    free = pos % guide.free_every == guide.free_every - 1
    assert np.all(raised[free] == guide.group) and np.all(raised[~free] == 1)


SESSIONS = {
    "loop": "closed", "system_prompt": 512,
    "sessions": {"turns": 8, "context_cap": 2048,
                 "user": {"length": {"dist": "lognormal", "mean": 96,
                                     "sigma": 0.7, "min": 4, "cap": 384}}},
    "output": {"length": {"dist": "lognormal", "mean": 105, "sigma": 0.7,
                          "min": 4, "cap": 256}},
    "guide": {"phase": 11, "bias_sigmas": 7.0}}


def test_sessions_grow_and_fit():
    """A closed-loop multi-turn mix (a data file a later cell can add)."""
    lim = Limits(1792, 2048, 33)
    m = Mix(SESSIONS, SEED, 32064, Guide.of(SESSIONS["guide"], 32064), lim)
    loop = m.clients(4)
    prev = {c: None for c in range(4)}
    last = {}
    seen_turns = set()
    for _ in range(60):
        for c in range(4):
            req = loop.next(c, prev[c])
            assert lim.fits(len(req.prompt), req.max_new)
            assert req.prompt[:len(m.system)] == m.system
            if prev[c] is not None and req.tag != "turn1":
                # the new turn holds the previous prompt and answer
                assert req.prompt[:len(last[c]) + len(prev[c])] == \
                    last[c] + prev[c]
            seen_turns.add(req.tag)
            prev[c] = list(range(2, 2 + req.max_new))
            last[c] = req.prompt
    assert "turn1" in seen_turns and "turn3" in seen_turns


def stream_digest(reqs, due=True):
    import hashlib
    h = hashlib.sha256()
    for r in reqs:
        row = [r.prompt, r.max_new] + ([round(r.due, 12)] if due else [])
        h.update(json.dumps(row + [r.tag]).encode())
    return h.hexdigest()


def test_a_mix_without_tenants_draws_as_before():
    """The rag stream of one seed, window and warm-up, hashed before the
    mix had tenants: the same requests in the same order."""
    t = mix_file("rag")
    guide = Guide.of(t["guide"], 151936)
    m = Mix(t, 9140000031, 151936, guide, Limits(1152, 1536, 33))
    reqs = m.open_loop(3.2, 50.0)
    assert len(reqs) == 160
    assert stream_digest(reqs) == (
        "ab98e845df2cffd6418c63577683a7353af26df68ccbfbe2f303a4ce82df9a3e")
    assert stream_digest(m.requests(32, 30), due=False) == (
        "551c165cfbfe455e9e058575adffaff4e4be8f75adb40209c95cf71d24b62211")
    assert {r.namespace for r in reqs} == {""}


def test_tenants_have_their_own_pools_and_prompts():
    t = mix_file("rag4")
    assert {k: v for k, v in t.items() if k not in ("tenants", "about")} \
        == {k: v for k, v in mix_file("rag").items() if k != "about"}
    guide = Guide.of(t["guide"], 151936)
    m = Mix(t, SEED, 151936, guide, Limits(1152, 1536, 33))
    reqs = m.open_loop(12.0, 20.0)
    names = [f"tenant{i}" for i in range(4)]
    assert m.namespaces == names
    # the shares, stratified: 60 requests from each of the four
    assert sorted({r.namespace for r in reqs}) == names
    assert all(sum(r.namespace == n for r in reqs) == 60 for n in names)
    sys_len = 64
    assert len({tuple(s) for s in m.systems}) == 4
    assert all(len(s) == sys_len for s in m.systems)
    for r in reqs:
        ti = names.index(r.namespace)
        assert r.prompt[:sys_len] == m.systems[ti]
        doc = m.document(int(r.tag[4:]), ti)
        assert r.prompt[sys_len:sys_len + len(doc)] == doc
    # the same rank is another document in another tenant's pool, of the
    # same length (the sizes are the mix's)
    for rank in (0, 1, 50):
        docs = [m.document(rank, ti) for ti in range(4)]
        assert len({len(d) for d in docs}) == 1
        assert len({tuple(d[:16]) for d in docs}) == 4
    # the sizes and arrivals are the mix's own, whatever the tenants
    plain = rag_mix()[0].open_loop(12.0, 20.0)
    assert [(r.max_new, r.tag, len(r.prompt), r.due) for r in reqs] == \
        [(r.max_new, r.tag, len(r.prompt), r.due) for r in plain]
    # one schedule for every seed: the same tenants in the same order
    other = Mix(t, 7, 151936, guide, Limits(1152, 1536, 33))
    assert [r.namespace for r in other.open_loop(12.0, 20.0)] == \
        [r.namespace for r in reqs]


def test_requests_carry_their_tenants_namespace():
    from harness.main import params_for

    class Cfg:
        from repro.core.draft_sources import DraftPolicy
        draft_policy = DraftPolicy(sources=("trie", "prompt_copy"))
    fn = params_for(Cfg(), "bench.warmup")
    t = mix_file("rag4")
    m = Mix(t, SEED, 151936, Guide.of(t["guide"], 151936),
            Limits(1152, 1536, 33))
    for r in m.requests(8, 30):
        p = fn(r)
        assert p.draft.namespace == r.namespace != ""
        assert p.draft.sources == ("trie", "prompt_copy")
        assert p.max_new_tokens == r.max_new
    plain = rag_mix()[0].requests(2, 30)[0]
    assert fn(plain).draft.namespace == "bench.warmup"
