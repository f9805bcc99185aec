"""Training rows from a mix's parameters and the seed.

Token ids drawn uniformly from the vocabulary, one generator per row index, so
a row is the same whoever asks for it and in whatever order (the seeding
follows ``repro.serve.workload.synthesize``: one ``numpy.random.default_rng``
per seed and stream).
"""

from __future__ import annotations

import numpy as np


class TrainRows:
    """``n_sequences`` rows of ``seq_len + 1`` uniform token ids; the
    ``batch(indices)`` interface of the program's datasets."""

    def __init__(self, vocab_size: int, seq_len: int, n_sequences: int, seed: int) -> None:
        self.vocab_size, self.seq_len, self.n_sequences, self.seed = vocab_size, seq_len, n_sequences, seed
        self.served: list[tuple[int, np.ndarray]] = []  # (clock(), indices) of every batch() call
        self.clock = lambda: 0

    def row(self, index: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 1, int(index)]))
        return rng.integers(0, self.vocab_size, self.seq_len + 1, dtype=np.int32)

    def batch(self, indices) -> dict:
        indices = np.asarray(indices) % self.n_sequences
        self.served.append((self.clock(), indices.copy()))
        seqs = np.stack([self.row(int(i)) for i in indices])
        return {"inputs": seqs[:, :-1], "targets": seqs[:, 1:]}

    def __len__(self) -> int:
        return self.n_sequences

