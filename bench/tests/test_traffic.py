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
