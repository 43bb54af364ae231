"""Traffic of a training cell: which stage of the deployment runs (or all of
them, one per chip), at what batch and sequence length, and the token
batches it is fed.

A traffic file (``bench/traffic/<name>.json``) holds only parameters; this
one generator reads them.  Tokens follow the program's synthetic corpus
(``data/lm.py``): a sparse bigram backbone, each token with ``branch``
possible successors, plus induction spans copied from earlier in the same
sequence.  This copy is vectorised over sequences in numpy and made from
the seed, so a pool of sequences costs milliseconds of set-up.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import List, Union

import numpy as np


@dataclass(frozen=True)
class Traffic:
    name: str
    stage: Union[int, str]   # stage index, negative from the last; "all"
    batch: int
    seq: int
    pool: int           # distinct sequences generated; step i takes rows
    branch: int         # successors per token in the bigram backbone
    repeat_p: float     # share of positions that start an induction span
    span: int           # longest induction span
    check_steps: int    # first steps compared with the reference

    @property
    def deployment(self) -> bool:
        """Every stage of the deployment at once, stage k on chip k."""
        return self.stage == "all"

    def stages(self, n_stages: int) -> List[int]:
        """The stages the cell runs: all of them, or the one it names."""
        if self.deployment:
            return list(range(n_stages))
        k = self.stage if self.stage >= 0 else n_stages + self.stage
        if not 0 <= k < n_stages:
            raise ValueError(f"traffic {self.name}: stage {self.stage} out of "
                             f"range for {n_stages} stages")
        return [k]


def load_traffic(path: str, name: str) -> Traffic:
    with open(path) as f:
        raw = json.load(f)
    t = Traffic(name=name, **{k: raw[k] for k in (
        "stage", "batch", "seq", "pool", "branch", "repeat_p", "span",
        "check_steps")})
    if not (isinstance(t.stage, int) or t.deployment):
        raise ValueError(f"traffic {name}: stage {t.stage!r} is neither a "
                         "stage index nor \"all\"")
    if t.pool < t.batch * t.check_steps:
        raise ValueError(f"traffic {name}: pool {t.pool} cannot give "
                         f"{t.check_steps} checked steps distinct rows")
    return t


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence((int(seed), stream))))


def token_pool(t: Traffic, vocab: int, seed: int) -> np.ndarray:
    """(pool, seq + 1) int32 token sequences made from ``seed``."""
    rng = _rng(seed, 0)
    n, length = t.pool, t.seq + 1
    succ = rng.integers(0, vocab, size=(min(vocab, 4096), t.branch))
    picks = rng.integers(0, t.branch, size=(length, n))
    out = np.empty((n, length), np.int64)
    cur = rng.integers(0, vocab, size=n)
    for i in range(length):
        out[:, i] = cur
        cur = succ[cur % succ.shape[0], picks[i]]
    # induction: copy spans of each sequence onto later positions of it
    n_spans = int(length * t.repeat_p / (t.span / 2))
    for r in range(n):
        for _ in range(n_spans):
            ln = int(rng.integers(4, t.span))
            dst = int(rng.integers(2 * t.span, length - ln))
            src = int(rng.integers(0, dst - ln))
            out[r, dst:dst + ln] = out[r, src:src + ln]
    return (out % vocab).astype(np.int32)


class Batches:
    """Step i's batch: rows of the pool in an order drawn from the seed, so
    the checked first steps see rows that all differ."""

    def __init__(self, t: Traffic, vocab: int, seed: int):
        self.t = t
        self.pool = token_pool(t, vocab, seed)
        self.order = _rng(seed, 1).permutation(t.pool)

    def rows(self, i: int) -> np.ndarray:
        b = self.t.batch
        idx = [self.order[(i * b + j) % self.t.pool] for j in range(b)]
        return self.pool[idx]

    def host(self, i: int) -> dict:
        """{'tokens', 'labels'}: (batch, seq) int32, labels the next token."""
        r = self.rows(i)
        return {"tokens": np.ascontiguousarray(r[:, :-1]),
                "labels": np.ascontiguousarray(r[:, 1:])}
