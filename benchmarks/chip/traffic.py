"""The one traffic generator: reads a mix (``traffic/<name>.json``) and a
seed, and makes the batches, round masks and arrival process of a run.

The generators are copies of the program's (``data/synthetic.py``
``make_token_sampler``, ``core/schedules.py`` ``truncated_normal_speeds`` /
``make_round_schedule``, ``runtime/arrivals.py`` ``FixedArrivals``), kept
here so that a change to the program cannot change the yardstick.

Every draw comes from ``numpy.random.SeedSequence(seed)``, so any whole
number is a seed, and the same seed gives the same traffic.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def seeds(seed: int) -> dict:
    """Independent 31-bit seeds for each use of one run seed."""
    ss = np.random.SeedSequence(int(seed))
    names = ("weights", "tokens", "speeds", "program")
    vals = ss.generate_state(len(names), np.uint32) & 0x7FFFFFFF
    return {k: int(v) for k, v in zip(names, vals)}


def make_token_sampler(n_workers, vocab, seq_len, batch, heterogeneity, seed):
    """Per-worker LM batches: worker i's unigram logits are shared +
    heterogeneity * private_i (per-worker skewed token streams)."""
    rng0 = np.random.default_rng(seed)
    shared = rng0.normal(0, 1, size=vocab)
    private = rng0.normal(0, 1, size=(n_workers, vocab))
    probs = []
    for i in range(n_workers):
        logit = shared + heterogeneity * private[i]
        p = np.exp(logit - logit.max())
        probs.append(p / p.sum())

    def sample(worker, rng):
        toks = rng.choice(vocab, size=(batch, seq_len + 1), p=probs[worker])
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}

    return sample


def truncated_normal_speeds(n, mu, std, seed, floor=1e-2) -> np.ndarray:
    """Per-worker seconds per gradient, s_i ~ TN(mu, std) (paper §5)."""
    rng = np.random.default_rng(seed)
    out = np.empty(n, dtype=np.float64)
    for i in range(n):
        t = rng.normal(mu, std)
        while t <= floor:
            t = rng.normal(mu, std)
        out[i] = t
    return out


def make_round_schedule(times: np.ndarray, rounds: int):
    """``(start, commit)`` masks ``[rounds, n]``: worker i starts a job,
    which commits ``ceil(t_i / min t)`` rounds later, then starts again."""
    n = times.shape[0]
    dur = np.maximum(1, np.ceil(times / times.min()).astype(np.int64))
    start = np.zeros((rounds, n), bool)
    commit = np.zeros((rounds, n), bool)
    for i in range(n):
        r = 0
        while r < rounds:
            start[r, i] = True
            fin = r + int(dur[i])
            if fin < rounds:
                commit[fin, i] = True
            r = fin
    return start, commit


class FixedArrivals:
    """Fixed-computation-speed arrivals: worker i always takes
    ``times[i]`` per gradient.  Has the interface the program's arrival
    loop drives (``n``, ``reset``, ``duration_at``, ``client_event``)."""

    def __init__(self, times):
        self.times = np.asarray(times, np.float64)
        self.n = int(self.times.shape[0])

    def reset(self) -> None:
        pass

    def duration(self, worker: int) -> float:
        return float(self.times[worker])

    def duration_at(self, worker: int, t: float) -> float:
        return self.duration(worker)

    def client_event(self, worker: int):
        return None


@dataclasses.dataclass
class Traffic:
    """One run's inputs: ``pool[j]`` is the j-th batch of every worker
    (leaves ``[n, batch, seq]``), plus the worker speeds."""

    mix: dict
    pool: list
    times: np.ndarray

    def tokens_per_batch(self) -> int:
        return self.mix["per_worker_batch"] * self.mix["seq_len"]


def make(mix: dict, vocab: int, seed: int) -> Traffic:
    s = seeds(seed)
    n = mix["n_workers"]
    sample = make_token_sampler(n, vocab, mix["seq_len"],
                                mix["per_worker_batch"],
                                mix["heterogeneity"], s["tokens"])
    rng = np.random.default_rng(s["tokens"] + 1)
    pool = []
    for _ in range(mix["pool"]):
        per = [sample(i, rng) for i in range(n)]
        pool.append({k: np.stack([p[k] for p in per]) for k in per[0]})
    times = truncated_normal_speeds(n, mix["speed_mu"], mix["speed_std"],
                                    s["speeds"])
    return Traffic(mix, pool, times)
