"""The benchmark's own copy of the training traffic generator.

A copy, not an import, of ``repro.data.pipeline.TokenPipeline``: documents
of log-normal length with Zipf-distributed token ids, packed greedily into
rows of ``seq + 1`` tokens behind a BOS token. Every batch is a pure
function of ``(seed, step)``. The harness compares every batch the program
fed against this generator, and the reference trains on its batches, so a
change to the program's traffic fails ``correct``.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


class Traffic:
    """``batch(step)`` → ``{"tokens", "labels"}``, each ``(batch, seq)``."""

    def __init__(self, *, vocab: int, seq: int, batch: int, seed: int,
                 zipf_a: float, doc_len_median: float, doc_len_sigma: float,
                 bos: int) -> None:
        self.vocab, self.seq, self.rows, self.seed = vocab, seq, batch, seed
        self.zipf_a, self.bos = zipf_a, bos
        self.doc_len_median, self.doc_len_sigma = doc_len_median, doc_len_sigma

    def _rng(self, step: int, row: int) -> np.random.Generator:
        # one data-parallel shard (shard id 0), as the one-host cells run
        return np.random.default_rng(
            (self.seed * 1_000_003 + step) * 65_537 + row)

    def _row(self, rng: np.random.Generator) -> np.ndarray:
        out = np.empty(self.seq + 1, np.int32)
        fill = 0
        while fill < self.seq + 1:
            n = int(rng.lognormal(np.log(self.doc_len_median),
                                  self.doc_len_sigma))
            n = max(8, min(n, self.seq))
            doc = rng.zipf(self.zipf_a, size=n).astype(np.int64)
            doc = doc % (self.vocab - 2) + 2      # 0 = pad, 1 = bos
            take = min(n + 1, self.seq + 1 - fill)
            out[fill] = self.bos
            out[fill + 1: fill + take] = doc[: take - 1]
            fill += take
        return out

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rows = np.stack([self._row(self._rng(step, r))
                         for r in range(self.rows)])
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


def traffic_for(config: dict, traffic: dict, seed: int) -> Traffic:
    d = traffic["data"]
    return Traffic(vocab=config["vocab_size"], seq=traffic["seq"],
                   batch=traffic["batch"], seed=seed, zipf_a=d["zipf_a"],
                   doc_len_median=d["doc_len_median"],
                   doc_len_sigma=d["doc_len_sigma"], bos=d["bos"])
