"""Inputs from the seed: token files for training, prompts for serving.

`write_token_file` is a copy of the program's `data/loader.
make_synthetic_bin` (noisy ramp over 1024 ids with 5% resets, so a loss can
fall), kept here so the program cannot change the benchmark's inputs.
"""

from __future__ import annotations

import os

import numpy as np


def write_token_file(path: str, n_tokens: int, vocab_size: int,
                     seed: int) -> str:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    rng = np.random.default_rng(seed)
    eff_vocab = min(vocab_size, 1024)
    walk = np.cumsum(rng.integers(-3, 4, size=n_tokens)) % eff_vocab
    noise = rng.integers(0, eff_vocab, size=n_tokens)
    toks = np.where(rng.random(n_tokens) < 0.05, noise, walk)
    tmp = f"{path}.tmp"
    toks.astype(np.uint16).tofile(tmp)
    os.replace(tmp, path)
    return path


def sample_tokens(seed: int, shape, vocab_size: int) -> np.ndarray:
    """Uniform random ids: the seeded sample the reference check runs on,
    and the unshared prompts of the serving mixes."""
    return np.random.default_rng(seed).integers(
        0, vocab_size, size=shape, dtype=np.int64).astype(np.int32)
