"""The traffic generator: deterministic from the seed, at a tiny size."""

import numpy as np
import pytest

from bench import gen

MIX = {"kind": "local", "batch": 4, "seq": 16, "skew": 3.0, "pool_batches": 3}
CFG = {"vocab_size": 64, "num_classes": 8}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_local_pool_is_fixed_by_the_seed(seed):
    a = gen.local_pool(MIX, CFG, seed)
    b = gen.local_pool(MIX, CFG, seed)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    tokens, labels = a
    assert tokens.shape == (3, 4, 16) and tokens.dtype == np.int32
    assert labels.shape == (3, 4)
    assert tokens.min() >= 0 and tokens.max() < 64
    assert labels.min() >= 0 and labels.max() < 8


def test_seeds_differ_but_sizes_do_not():
    a, _ = gen.local_pool(MIX, CFG, 1)
    b, _ = gen.local_pool(MIX, CFG, 2)
    assert a.shape == b.shape and not np.array_equal(a, b)


def test_class_bias_matches_the_loop_draw():
    # the vectorised draw has the distribution of the per-sample loop in
    # repro.data.synthetic.token_classification: block ids e^skew as likely
    r = gen.rng(5, "t")
    vocab, classes, skew = 64, 8, 3.0
    labels = np.full((20000,), 3)
    toks = gen.class_biased_tokens(r, labels, 1, vocab, classes, skew)[:, 0]
    block = vocab // classes
    w = np.ones(vocab)
    w[3 * block:4 * block] *= np.exp(skew)
    want = w / w.sum()
    got = np.bincount(toks, minlength=vocab) / len(toks)
    assert np.max(np.abs(got - want)) < 0.01


@pytest.mark.parametrize("seed", [3, 2**40 + 3])
def test_prefold_is_fixed_by_the_seed(seed):
    cfg = dict(CFG, d_model=10)
    rows, labels = gen.prefold(MIX, cfg, seed)
    assert rows.shape == (3, 4, 10) and rows.dtype == np.float32
    assert labels.shape == (3, 4) and 0 <= labels.min() <= labels.max() < 8
    again = gen.prefold(MIX, cfg, seed)
    np.testing.assert_array_equal(rows, again[0])
    np.testing.assert_array_equal(labels, again[1])


def test_check_rows_cover_every_batch():
    picks = gen.check_rows(10, 8, 25, seed=4)
    assert picks == gen.check_rows(10, 8, 25, seed=4)
    assert len(picks) == 30 and len(set(picks)) == 30
    assert {b for b, _ in picks} == set(range(10))
    assert all(0 <= r < 8 for _, r in picks)
    # never more rows than a batch holds
    assert len(gen.check_rows(2, 8, 100, seed=4)) == 16
