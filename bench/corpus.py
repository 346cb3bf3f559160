"""Tokens for the cells, made on the host from a traffic file and ``--seed``
before the measured window.

A copy of the program's phased synthetic corpus (``repro.data.synthetic``):
tokens come from domains, each a band of the vocabulary with its own Zipf
exponent.  Training batches follow a cyclic schedule of one phase per
domain followed by an even blend; serving prompts draw their domain evenly.
Every domain and every phase length comes from the traffic file.  Batch
``step`` is a function of (seed, step) alone, so a seed gives the same
tokens in every run.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


class Domains:
    """Rows of token ids from the traffic file's ``domains``."""

    def __init__(self, domains: List[Dict], vocab_size: int):
        self.cdfs = []
        for d in domains:
            lo = int(d["vocab_lo"] * vocab_size)
            hi = max(lo + 2, int(d["vocab_hi"] * vocab_size))
            w = np.arange(1, hi - lo + 1, dtype=np.float64) ** (-d["zipf_a"])
            cdf = np.cumsum(w)
            self.cdfs.append((lo, cdf / cdf[-1]))

    def __len__(self) -> int:
        return len(self.cdfs)

    def row(self, rng: np.random.Generator, domain: int,
            length: int) -> np.ndarray:
        lo, cdf = self.cdfs[domain]
        idx = np.searchsorted(cdf, rng.random(length))
        return (lo + np.minimum(idx, len(cdf) - 1)).astype(np.int32)


class Corpus:
    """Training batches for steps ``0 .. n_steps-1``, each ``{"tokens",
    "labels"}`` of shape ``[batch, seq_len]`` (int32, labels shifted by
    one)."""

    def __init__(self, traffic: Dict, vocab_size: int, seed: int,
                 n_steps: int):
        self.seq_len = int(traffic["seq_len"])
        self.batch = int(traffic["batch"])
        self.seed = seed
        self.domains = Domains(traffic["domains"], vocab_size)
        self._mixes = self._schedule(traffic, len(self.domains))
        self._batches: List[Dict[str, np.ndarray]] = []
        self.extend(n_steps)

    @staticmethod
    def _schedule(traffic: Dict, n: int) -> List[np.ndarray]:
        """One mix per step of a schedule cycle: ``phase_steps`` steps led by
        each domain in turn (``major_share`` of it, the rest spread evenly),
        then ``blend_steps`` of an even blend."""
        major = traffic["major_share"]
        mixes = []
        for i in range(n):
            m = np.full(n, (1.0 - major) / n)
            m[i] += major
            mixes += [m / m.sum()] * traffic["phase_steps"]
        return mixes + [np.full(n, 1.0 / n)] * traffic["blend_steps"]

    def extend(self, n_steps: int) -> None:
        """Make batches up to step ``n_steps - 1``."""
        for step in range(len(self._batches), n_steps):
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, step]))
            mix = self._mixes[step % len(self._mixes)]
            rows = rng.choice(len(self.domains), size=self.batch, p=mix)
            toks = np.stack([self.domains.row(rng, int(d), self.seq_len + 1)
                             for d in rows])
            self._batches.append({"tokens": toks[:, :-1],
                                  "labels": toks[:, 1:]})

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        return self._batches[step]
