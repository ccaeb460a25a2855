"""Token rows of a traffic mix, made from the run's seed.

The arithmetic is that of ``repro.data.SyntheticLMStream``, copied here so
that a change to the program cannot change what the benchmark feeds it:
t_{i+1} = (a * t_i + b + i mod period) mod V, except where a fresh random
token replaces it (share ``noise``). ``batch_at(step)`` is a pure function
of (seed, step, shard), so the reference regenerates the rows that the
timed path was fed.
"""
from __future__ import annotations

import numpy as np


class MarkovLM:
    """One worker's shard of the stream: ``batch_at(step)`` gives
    ``{"tokens", "targets", "mask"}``, each (batch, seq)."""

    def __init__(self, data: dict, vocab: int, seq: int, batch: int,
                 seed: int, shard: int, n_shards: int):
        self.a = data["a"] % vocab or 1
        self.b = data["b"]
        self.period = data["period"]
        self.noise = data["noise"]
        self.vocab, self.seq, self.batch = vocab, seq, batch
        self.seed, self.shard, self.n_shards = seed, shard, n_shards

    def batch_at(self, step: int) -> dict:
        rng = np.random.RandomState(
            (self.seed * 1_000_003 + step * self.n_shards + self.shard)
            % (2**31 - 1))
        B, S, V = self.batch, self.seq, self.vocab
        t0 = rng.randint(0, V, size=(B, 1))
        noise = (rng.rand(B, S) < self.noise) * rng.randint(0, V, size=(B, S))
        toks = [t0]
        for i in range(1, S):
            nxt = (self.a * toks[-1] + self.b + (i % self.period)) % V
            toks.append(np.where(noise[:, i:i + 1] > 0,
                                 noise[:, i:i + 1] % V, nxt))
        tokens = np.concatenate(toks, axis=1).astype(np.int32)
        targets = np.concatenate(
            [tokens[:, 1:], tokens[:, :1]], axis=1).astype(np.int32)
        mask = np.ones((B, S), np.float32)
        mask[:, -1] = 0.0
        return {"tokens": tokens, "targets": targets, "mask": mask}


def worker_batches(traffic: dict, vocab: int, seed: int, step: int) -> list:
    """Each worker's rows at ``step``, in worker order."""
    n = traffic["workers"]
    return [MarkovLM(traffic["data"], vocab, traffic["seq"],
                     traffic["batch_per_worker"], seed, i, n).batch_at(step)
            for i in range(n)]
