"""Training-token traffic, generated from a parameter file and the seed.

One generator serves every mix.  A mix file (``bench/traffic/<name>.json``)
sets:

- ``zipf_s``: token ranks follow Zipf's law with this exponent, truncated at
  the vocabulary size (0 gives uniform tokens);
- ``topics``: each document draws one of this many topics; a topic is a
  seeded permutation of the vocabulary that maps ranks to token ids;
- ``doc_len_median``, ``doc_len_sigma``, ``doc_len_min``, ``doc_len_max``:
  lognormal document lengths, clipped, packed back to back into rows of
  ``seq + 1`` tokens (labels are the tokens shifted by one).

Batch ``k`` of a seed is a pure function of ``(seed, k)``: every seed gets
the same batch shape, and fresh tokens each step.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

MIX_KEYS = ("zipf_s", "topics", "doc_len_median", "doc_len_sigma",
            "doc_len_min", "doc_len_max")


class TokenStream:
    def __init__(self, mix: Dict, vocab: int, batch: int, seq: int,
                 seed: int):
        missing = [k for k in MIX_KEYS if k not in mix]
        if missing:
            raise ValueError(f"traffic mix lacks {missing}")
        self.mix, self.vocab, self.batch, self.seq = mix, vocab, batch, seq
        self.seed = int(seed)
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(mix["zipf_s"])
        self._cdf = np.cumsum(p / p.sum())
        self._cdf[-1] = 1.0
        rng = np.random.default_rng([self.seed, 0x70])
        self._topics = np.stack(
            [rng.permutation(vocab).astype(np.int32)
             for _ in range(int(mix["topics"]))]
        )

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        """``{"tokens", "labels"}``, each ``(batch, seq)`` int32."""
        m = self.mix
        rng = np.random.default_rng([self.seed, 0x71, int(step)])
        need = self.batch * (self.seq + 1)
        out = np.empty(need, np.int32)
        filled = 0
        while filled < need:
            n = int(np.clip(
                round(rng.lognormal(np.log(m["doc_len_median"]),
                                    m["doc_len_sigma"])),
                m["doc_len_min"], m["doc_len_max"],
            ))
            n = min(n, need - filled)
            topic = self._topics[rng.integers(len(self._topics))]
            ranks = np.searchsorted(self._cdf, rng.random(n), side="right")
            out[filled:filled + n] = topic[np.minimum(ranks, self.vocab - 1)]
            filled += n
        rows = out.reshape(self.batch, self.seq + 1)
        return {"tokens": rows[:, :-1].copy(), "labels": rows[:, 1:].copy()}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1
