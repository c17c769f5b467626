"""The benchmark's own copy of the token-stream generator.

A language-model cell's inputs are ``synthetic://tokens`` URIs whose
parameters come from the configuration file and ``--seed``; the program
turns a URI into arrays with ``rafiki_tpu.model.dataset.synthetic_tokens``.
The reference may take nothing the program made, so it makes the same
arrays here from the same parameters. Copied from that function;
``benchmark/tests/test_lm_references.py`` pins the two against each other.
Seeds of a run's train and validation draws: ``datagen.data_seeds``.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def token_uri(cfg: dict, n: int, seed: int) -> str:
    return (f"synthetic://tokens?vocab={cfg['vocab_size']}&n={n}"
            f"&len={cfg['seq_len']}&seed={seed}&follow={cfg['follow']}")


def synthetic_tokens(vocab: int, n: int, length: int, seed: int, follow: float,
                     dist: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    law = np.random.default_rng(dist + 7_000_003)
    rank_of = law.permutation(vocab)
    successor = law.permutation(vocab)
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    p /= p.sum()
    rng = np.random.default_rng(seed + 1_000_003)
    free = rank_of[rng.choice(vocab, size=(n, length + 1), p=p)]
    bound = rng.uniform(size=(n, length + 1)) < follow
    toks = free.copy()
    for t in range(1, length + 1):
        toks[:, t] = np.where(bound[:, t], successor[toks[:, t - 1]], free[:, t])
    toks = toks.astype(np.int32)
    return toks[:, :-1].copy(), toks[:, 1:].copy()


def tokens_of(cfg: dict, n: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(x, y): ``n`` documents of the configuration's length and the token
    that follows each position."""
    return synthetic_tokens(int(cfg["vocab_size"]), n, int(cfg["seq_len"]), seed,
                            float(cfg["follow"]))
