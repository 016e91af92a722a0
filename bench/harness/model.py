"""What every model family shares: the seed's PRNG key, and the guide
bias a cell's traffic asks for.

The family (``bench/families/<family>.py``) gives a configuration's shape,
its weights and its program configuration; the bias here only needs the
shape's ``d_model`` and ``init_std``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed, 64-bit ones included."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ------------------------------------------------------------------- guide
def make_guide(vocab: int, phase: int, seed: int, group: int = 1
               ) -> np.ndarray:
    """(phase, vocab) successor table: 70% of (phase, token) entries share
    one phase-independent successor, so walks share chains and part ways
    at about 30% of steps (trie drafts find branches to verify).  No
    successor lies in the first ``group`` tokens, so no candidate group
    holds the pad or the first tokens of the vocabulary."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 1000 + phase])
    lo = max(2, int(group))
    base = rng.integers(lo, vocab, size=(vocab,))
    spec = rng.integers(lo, vocab, size=(phase, vocab))
    shared = rng.random((phase, vocab)) < 0.7
    return np.where(shared, base[None, :], spec).astype(np.int32)


@dataclass(frozen=True, eq=False)
class Guide:
    """The guide a mix serves under: a successor table, and the positions
    the model decides for itself.

    The token after a position ``p`` with ``p % free_every == free_every -
    1`` is a free choice: the bias goes to every token of the successor's
    group (``group`` consecutive ids), so the model's own logits pick among
    them, and any fault or loss of precision in them shows in the served
    token.  The successor of a freely chosen token is that of its group's
    first member, so every choice leads back onto the same walk and the
    answer keeps copying its document."""
    table: np.ndarray
    free_every: int = 0
    group: int = 1

    @classmethod
    def of(cls, spec: Dict[str, Any], vocab: int) -> "Guide":
        """The guide a mix's ``guide`` entry describes."""
        group = int(spec.get("free_group", 1))
        return cls(make_guide(vocab, int(spec["phase"]),
                              int(spec.get("seed", 0)), group),
                   free_every=int(spec.get("free_every", 0)), group=group)

    def __post_init__(self):
        if self.free_every and self.table.shape[1] % self.group:
            raise ValueError(f"group {self.group} does not divide the "
                             f"vocabulary {self.table.shape[1]}")

    @property
    def phase(self) -> int:
        return int(self.table.shape[0])

    @property
    def period(self) -> int:
        """Positions after which the guide's rules repeat."""
        return math.lcm(self.phase, self.free_every or 1)

    def successor(self, tokens, positions, xp=np):
        """The guide's next token after ``tokens`` at ``positions``."""
        src = tokens
        if self.free_every:
            chosen = positions % self.free_every == 0
            src = xp.where(chosen, tokens // self.group * self.group, tokens)
        return xp.asarray(self.table)[positions % self.phase, src]

    def candidates(self, tokens, positions, xp=np):
        """(..., group) token ids and whether each gets the bias: the
        successor alone, or at a free position its whole group.  Ids past
        the vocabulary are clipped and get nothing."""
        nxt = self.successor(tokens, positions, xp)
        if not self.free_every:
            return nxt[..., None], xp.ones(nxt.shape + (1,), bool)
        k = xp.arange(self.group)
        free = positions % self.free_every == self.free_every - 1
        first = xp.where(free, nxt // self.group * self.group, nxt)
        ids = xp.minimum(first[..., None] + k, self.table.shape[1] - 1)
        return ids, free[..., None] | (k == 0)


def bias_size(shape: Dict[str, Any], sigmas: float) -> float:
    """The guide's logit bias in units of the random model's logit spread
    (rms-normed hidden state times N(0, std) head columns)."""
    return float(sigmas) * float(np.sqrt(shape["d_model"])) * shape[
        "init_std"]


def make_bias(guide: Guide, size: float) -> Callable:
    """``logits_transform(logits, tokens, positions)``: add ``size`` to the
    logits of the guide's candidates after (token, position).  A pure
    function of (token, position), so the served tokens stay lossless;
    computed in float32 so the bias adds no rounding of its own."""

    def bias(logits, tokens, positions):
        lg = logits.astype(jnp.float32)
        ids, on = guide.candidates(tokens, positions, jnp)     # (B, T, k)
        add = jnp.where(on, jnp.float32(size), jnp.float32(0))
        B, T = tokens.shape
        return lg.at[jnp.arange(B)[:, None, None],
                     jnp.arange(T)[None, :, None], ids].add(add)

    return bias


def add_bias(logits: np.ndarray, guide: Optional[Guide], tokens: np.ndarray,
             positions: np.ndarray, size: float) -> np.ndarray:
    """The same bias on the host, in float32: row i predicts the token
    after ``tokens[i]`` at ``positions[i]``."""
    if guide is None:
        return logits
    ids, on = guide.candidates(tokens, positions)
    out = logits.copy()
    rows = np.broadcast_to(np.arange(len(ids))[:, None], ids.shape)
    np.add.at(out, (rows, ids), np.where(on, np.float32(size),
                                         np.float32(0)))
    return out


def guide_walk(guide: Guide, start: int, pos0: int, n: int) -> np.ndarray:
    """n tokens that follow the guide from ``start`` at absolute position
    ``pos0``: token i+1 is ``guide.successor`` of token i at position
    pos0 + i (at a free position, the successor itself, one of its
    group)."""
    table, every, group = guide.table, guide.free_every, guide.group
    out = np.empty((n,), np.int32)
    t = int(start)
    for i in range(n):
        out[i] = t
        p = pos0 + i
        src = t // group * group if every and p % every == 0 else t
        t = int(table[p % guide.phase, src])
    return out
