"""Data pipeline tests: prepare scripts -> .bin -> DataLoader round trip
(the reference has no tests for its ETL; SURVEY.md §4)."""

import os

import numpy as np
import pytest

from distributed_pytorch_tpu.data.loader import (DataLoader,
                                                 make_synthetic_bin,
                                                 philox_offsets)
from distributed_pytorch_tpu.data import prepare_shakespeare, prepare_tinystories
from distributed_pytorch_tpu.data.prepare import get_tokenizer


CORPUS = "\n\n".join(
    f"Once upon a time there was a number {i}. It liked to count. The end."
    for i in range(200))


@pytest.fixture
def corpus_file(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_text(CORPUS, encoding="utf-8")
    return str(p)


def test_prepare_shakespeare_local(tmp_path, corpus_file):
    out = str(tmp_path / "shakespeare")
    prepare_shakespeare.main(["--out_dir", out, "--input", corpus_file])
    train = np.fromfile(os.path.join(out, "train.bin"), dtype=np.uint16)
    val = np.fromfile(os.path.join(out, "val.bin"), dtype=np.uint16)
    assert train.size > 0 and val.size > 0
    # 90/10 contiguous split (reference prepare.py:21-23)
    assert abs(train.size / (train.size + val.size) - 0.9) < 0.01


def test_prepare_tinystories_local(tmp_path, corpus_file):
    out = str(tmp_path / "tinystories")
    prepare_tinystories.main(["--out_dir", out, "--input", corpus_file])
    train = np.fromfile(os.path.join(out, "train.bin"), dtype=np.uint16)
    val = np.fromfile(os.path.join(out, "val.bin"), dtype=np.uint16)
    assert train.size > 0 and val.size > 0
    _, eot, _ = get_tokenizer()
    # every story is EOT-terminated (reference prepare.py:36)
    assert train[-1] == eot and val[-1] == eot


def test_prepared_bin_feeds_loader(tmp_path, corpus_file):
    out = str(tmp_path / "ts")
    prepare_tinystories.main(["--out_dir", out, "--input", corpus_file])
    loader = DataLoader(os.path.join(out, "train.bin"), batch_size=2,
                        block_size=16, grad_accum=2)
    x, y = loader.next_batch()
    assert x.shape == (2, 2, 16) and y.shape == (2, 2, 16)
    assert (np.asarray(x[:, :, 1:]) == np.asarray(y[:, :, :-1])).all()


def test_loader_deterministic_across_process_counts(tmp_path):
    """The counter-based RNG must give the same global batch regardless of
    who samples it (resharding-stable, unlike the reference's +rank seed
    offset, multi-gpu/ddp/train.py:28-29)."""
    path = make_synthetic_bin(str(tmp_path / "det_test.bin"),
                              n_tokens=2 ** 14)
    a = DataLoader(path, 4, 32, grad_accum=2, seed=7)
    b = DataLoader(path, 4, 32, grad_accum=2, seed=7)
    xa, ya = a.next_batch()
    xb, yb = b.next_batch()
    assert (np.asarray(xa) == np.asarray(xb)).all()


@pytest.fixture(scope="module")
def bin_path(tmp_path_factory):
    p = tmp_path_factory.mktemp("loader") / "train.bin"
    return make_synthetic_bin(str(p), n_tokens=2 ** 15)


def test_loader_works_with_defaults(bin_path):
    loader = DataLoader(bin_path, 2, 16)
    x, y = loader.next_batch()
    assert x.shape == (1, 2, 16)
    assert (np.asarray(x)[:, :, 1:] == np.asarray(y)[:, :, :-1]).all()


def test_philox_known_answer():
    """Philox4x32-10 with a zero counter and a zero key (Random123's
    kat_vectors): 6627e8d5 e169c58d bc57ac4c 9b00dbd8. The draw is the
    first two words, the low word first."""
    assert int(philox_offsets(0, 0, [0], 2 ** 32)[0]) == 0x6627e8d5
    assert int(philox_offsets(0, 0, [0], 2 ** 63)[0]) == (
        (0xe169c58d << 32 | 0x6627e8d5) % 2 ** 63)


def test_seed_step_and_row_each_change_the_draw():
    rows = np.arange(64)
    a = philox_offsets(1729, 3, rows, 2 ** 40)
    assert len(set(a.tolist())) == 64
    assert (a != philox_offsets(1729, 4, rows, 2 ** 40)).all()
    assert (a != philox_offsets(42, 3, rows, 2 ** 40)).all()
    # the high words of a 64-bit step and seed are counter and key too
    assert (a != philox_offsets(1729, 3 + 2 ** 32, rows, 2 ** 40)).all()
    assert (a != philox_offsets(1729 + 2 ** 32, 3, rows, 2 ** 40)).all()
    assert (0 <= philox_offsets(2 ** 63, 2 ** 40, rows, 7)).all()
    assert (philox_offsets(2 ** 63, 2 ** 40, rows, 7) < 7).all()


def test_a_row_subset_equals_the_full_batchs_rows(bin_path):
    """What a process that owns a shard relies on: any rows of the global
    batch, gathered alone, are the rows the whole batch holds."""
    loader = DataLoader(bin_path, 4, 32, grad_accum=2, seed=7)
    x_full, y_full = loader._sample(5, np.arange(8))
    rows = np.array([1, 3, 6])
    x_sub, y_sub = loader._sample(5, rows)
    assert (x_sub == x_full[rows]).all() and (y_sub == y_full[rows]).all()


def test_steps_out_of_order_equal_steps_in_order(bin_path):
    """A resumed run asks for step k first: no state but (seed, step)."""
    in_order = DataLoader(bin_path, 4, 16, seed=9)
    seq = [in_order.next_batch() for _ in range(5)]
    cold = DataLoader(bin_path, 4, 16, seed=9)
    for step in (4, 2, 0):
        x, y = cold.next_batch(step)
        assert (x == seq[step][0]).all() and (y == seq[step][1]).all()
    assert cold.step == 1                 # the last call's step, plus one


def test_inputs_and_targets_are_the_files_windows_one_apart(bin_path):
    loader = DataLoader(bin_path, 4, 32, seed=11)
    x, y = loader.next_batch(3)
    tokens = np.fromfile(bin_path, dtype=np.uint16)
    offsets = philox_offsets(11, 3, np.arange(4), len(tokens) - 32 - 1)
    for row, o in enumerate(offsets):
        assert (x[0, row] == tokens[o:o + 32]).all()
        assert (y[0, row] == tokens[o + 1:o + 33]).all()


def test_prepare_fineweb_local(tmp_path, corpus_file):
    """fineweb prepare (the dataset the reference declares but never ships,
    single-gpu/train.sh:6): streaming writer produces loader-compatible
    bins with a deterministic 1% doc holdout."""
    from distributed_pytorch_tpu.data import prepare_fineweb
    out = str(tmp_path / "fineweb")
    prepare_fineweb.main(["--out_dir", out, "--input", corpus_file,
                          "--limit", "150"])
    train = np.fromfile(os.path.join(out, "train.bin"), dtype=np.uint16)
    val = np.fromfile(os.path.join(out, "val.bin"), dtype=np.uint16)
    assert train.size > 0 and val.size > 0
    _, eot, _ = get_tokenizer()
    assert train[-1] == eot and val[-1] == eot
    # docs 0 and 100 of the 150 -> exactly 2 val documents (2 EOTs)
    assert int((val == eot).sum()) == 2
    # and no leftover .part files (atomic promote)
    assert not [f for f in os.listdir(out) if ".part" in f]
    loader = DataLoader(os.path.join(out, "train.bin"), batch_size=2,
                        block_size=16, grad_accum=1)
    x, y = loader.next_batch()
    assert x.shape == (1, 2, 16)
