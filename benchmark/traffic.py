"""The one general traffic generator. A mix or a job is a data file under
`benchmark/traffic/`; this file turns its parameters and `--seed` into
training rows or a schedule of requests. The program sees only what is
generated.

The seed never chooses WHICH lengths or HOW MANY arrivals a run gets, only
their order, their instants and the token ids: lengths are the stratified
quantiles of the stated distribution, and open-loop arrivals are a Poisson
process conditioned on its count (n sorted uniforms over the window). So
every seed does the same work in another order.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_ROW, _DOC, _FRESH, _ORDER, _ARRIVE = 1, 2, 3, 4, 5      # rng stream tags


def rng(seed: int, *tags):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *map(int, tags)])


def train_row(seed: int, i: int, vocab: int, seq: int):
    """Row i of the job's token stream: every row differs."""
    return rng(seed, _ROW, i).integers(0, vocab, seq, dtype=np.int32)


def quantile(dist: dict, q: float) -> int:
    """The q-quantile of a length distribution, clipped to [min, max]."""
    if dist["dist"] == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * NormalDist().inv_cdf(q))
    elif dist["dist"] == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    elif dist["dist"] == "fixed":
        x = dist["value"]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(round(x), dist.get("min", 1)),
                   dist.get("max", 1 << 30)))


def stratified(dist: dict, n: int) -> list:
    """n lengths: the quantiles at (i + 1/2) / n. The same for every seed."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


class Request:
    __slots__ = ("index", "due_s", "doc", "doc_tokens", "fresh_tokens",
                 "new_tokens", "seed", "vocab")

    def __init__(self, index, due_s, doc, doc_tokens, fresh_tokens,
                 new_tokens, seed, vocab):
        self.index, self.due_s, self.doc = index, due_s, doc
        self.doc_tokens, self.fresh_tokens = doc_tokens, fresh_tokens
        self.new_tokens, self.seed, self.vocab = new_tokens, seed, vocab

    @property
    def prompt_len(self) -> int:
        return self.doc_tokens + self.fresh_tokens

    def prompt(self) -> list:
        """Shared document (if any) then fresh ids nobody else sends."""
        head = [] if self.doc is None else rng(
            self.seed, _DOC, self.doc).integers(
                0, self.vocab, self.doc_tokens).tolist()
        return head + rng(self.seed, _FRESH, self.index).integers(
            0, self.vocab, self.fresh_tokens).tolist()


def _block(mix: dict, n: int, seed: int, block: int, vocab: int,
           first_index: int, due) -> list:
    """n requests whose lengths are the mix's n stratified quantiles,
    shuffled by the seed (prompt, output and document independently)."""
    r = rng(seed, _ORDER, block)
    fresh = r.permutation(stratified(mix["fresh"], n))
    out = r.permutation(stratified(mix["output"], n))
    shared = mix.get("shared")
    if shared:
        n_doc = shared["documents"]
        doc_len = stratified(shared["tokens"], n_doc)
        docs = r.permutation([i % n_doc for i in range(n)])
    reqs = []
    for j in range(n):
        d = int(docs[j]) if shared else None
        reqs.append(Request(first_index + j, due[j], d,
                            doc_len[d] if shared else 0, int(fresh[j]),
                            int(out[j]), seed, vocab))
    return reqs


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """round(rate x seconds) requests, due at sorted uniform instants."""
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    due = np.sort(rng(seed, _ARRIVE).uniform(0.0, seconds, n)).tolist()
    return _block(mix, n, seed, 0, vocab, 0, due)


def closed_loop(mix: dict, seed: int, vocab: int):
    """An endless schedule for `clients` callers that each send their next
    request when the last one is answered: consecutive blocks of `clients`
    requests, each block the stratified quantiles in a seeded order, so
    any prefix of the schedule holds the same mix of lengths."""
    n, block = mix["clients"], 0
    while True:
        yield from _block(mix, n, seed, block, vocab, block * n, [None] * n)
        block += 1
