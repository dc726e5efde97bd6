"""Memmap token loader.

Reference parity (`DataLoader`, single-gpu/train.py:210-254): np.memmap of a
raw uint16 token file; every batch = B *uniform-random* start offsets (not
sequential epochs); y is x shifted by one. The reference decorrelates DDP
ranks purely via a +rank seed offset (multi-gpu/ddp/train.py:28-29); here
every process samples from one counter-based RNG stream keyed by
(seed, step, accum-slot, row) so the global batch is identical regardless of
process count — resharding-stable and resumable (a capability the reference
lacks: its loader state is unrecoverable RNG).

TPU-first: the loader returns the whole optimizer-step batch (accum, B, T)
and places it into its mesh shards in one `device_put` — per-host, each
process materializes only its addressable slice (multi-host path via
`jax.make_array_from_process_local_data`).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding


def make_synthetic_bin(path: str, n_tokens: int = 2 ** 20,
                       vocab_size: int = 50304, seed: int = 1729) -> str:
    """Write a synthetic uint16 token file with mild Markov structure (so
    loss can actually decrease — pure uniform noise has nothing to learn).
    Used by tests and by the trainer's `--dataset synthetic` when no
    prepared dataset exists (this environment has no network egress for
    the real downloads)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    rng = np.random.default_rng(seed)
    eff_vocab = min(vocab_size, 1024)
    # tokens follow a noisy ramp: next ~ prev + small step (mod eff_vocab),
    # with 5% uniform-noise resets
    walk = np.cumsum(rng.integers(-3, 4, size=n_tokens)) % eff_vocab
    noise = rng.integers(0, eff_vocab, size=n_tokens)
    toks = np.where(rng.random(n_tokens) < 0.05, noise, walk)
    # write-to-temp + atomic rename: a killed run can't leave a partial
    # .bin, and concurrent processes (multi-host shared data_dir) see
    # either the old complete file or the new one, never a torn write
    tmp = f"{path}.tmp.{os.getpid()}"
    toks.astype(np.uint16).tofile(tmp)
    os.replace(tmp, path)
    return path


_M0 = np.uint64(0xD2511F53)
_M1 = np.uint64(0xCD9E8D57)
_MASK32 = np.uint64(0xFFFFFFFF)


def philox_offsets(seed: int, step: int, rows: np.ndarray,
                   hi: int) -> np.ndarray:
    """Philox4x32-10 offsets in [0, hi) for global batch-row ids `rows` at
    (seed, step): counter (row, step lo, step hi, 0), key (seed lo, seed
    hi), the draw the counter's first two words."""
    rows = np.asarray(rows, np.uint32)
    c0 = rows.astype(np.uint64)
    c1 = np.full_like(c0, np.uint64(step & 0xFFFFFFFF))
    c2 = np.full_like(c0, np.uint64((step >> 32) & 0xFFFFFFFF))
    c3 = np.zeros_like(c0)
    k0 = seed & 0xFFFFFFFF          # python ints: explicit mod-2^32 adds
    k1 = (seed >> 32) & 0xFFFFFFFF
    for _ in range(10):
        p0 = _M0 * c0          # 64-bit products (c in [0, 2^32))
        p1 = _M1 * c2
        hi0, lo0 = p0 >> np.uint64(32), p0 & _MASK32
        hi1, lo1 = p1 >> np.uint64(32), p1 & _MASK32
        c0, c1, c2, c3 = (hi1 ^ c1 ^ np.uint64(k0), lo1,
                          hi0 ^ c3 ^ np.uint64(k1), lo0)
        k0 = (k0 + 0x9E3779B9) & 0xFFFFFFFF
        k1 = (k1 + 0xBB67AE85) & 0xFFFFFFFF
    u = (c1 << np.uint64(32)) | c0
    return (u % np.uint64(hi)).astype(np.int64)


class DataLoader:
    """Random-offset batch sampler over a uint16 token memmap."""

    def __init__(self, file_path: str, batch_size: int, block_size: int, *,
                 grad_accum: int = 1, seed: int = 1729,
                 mesh=None, pspec=None):
        self.tokens = np.memmap(file_path, dtype=np.uint16, mode="r")
        assert len(self.tokens) > block_size + 1, (
            f"dataset {file_path} too small: {len(self.tokens)} tokens "
            f"<= block_size+1")  # reference train.py:221-222
        self.B, self.T, self.A = batch_size, block_size, grad_accum
        self.seed = seed
        self.step = 0
        self.mesh = mesh
        self.pspec = pspec
        self._sharding = (NamedSharding(mesh, pspec)
                         if mesh is not None and pspec is not None else None)

    def _sample(self, step: int, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Gather (len(rows), T) x/y pairs for global batch-row ids `rows` at
        `step`. Counter-based (Philox4x32-10) keyed on (seed, step, row): any
        process can materialize any subset of the global batch
        deterministically."""
        hi = len(self.tokens) - self.T - 1
        offsets = philox_offsets(self.seed, step, rows, hi)
        idx = offsets[:, None] + np.arange(self.T + 1)[None, :]
        seqs = self.tokens[idx].astype(np.int32)
        return seqs[:, :-1], seqs[:, 1:]

    def next_batch(self, step: Optional[int] = None):
        """Return (x, y), each (A, B, T) int32, sharded onto the mesh."""
        step = self.step if step is None else step
        self.step = step + 1

        if self._sharding is None:
            rows = np.arange(self.A * self.B)
            x, y = self._sample(step, rows)
            shp = (self.A, self.B, self.T)
            return x.reshape(shp), y.reshape(shp)

        # Sharded: materialize each addressable shard directly from the
        # memmap — on multi-host, a process never touches rows it doesn't
        # own; on one process this is just a sharded device_put.
        sh = self._sharding
        global_shape = (self.A, self.B, self.T)

        def shard(index, which: int):
            a_sl, b_sl, t_sl = index
            accums = np.arange(self.A)[a_sl]
            rows = np.arange(self.B)[b_sl]
            grid = (accums[:, None] * self.B + rows[None, :]).reshape(-1)
            x, y = self._sample(step, grid)
            shp = (len(accums), len(rows), self.T)
            out = (x, y)[which].reshape(shp)
            return out[..., t_sl]

        xs = jax.make_array_from_callback(global_shape, sh,
                                          lambda i: shard(i, 0))
        ys = jax.make_array_from_callback(global_shape, sh,
                                          lambda i: shard(i, 1))
        return xs, ys
