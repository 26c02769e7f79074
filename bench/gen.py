"""The one traffic generator: every mix under ``traffic/`` is parameters
for it, and every draw comes from ``--seed``.

``local`` mixes: one client's data, a pool of ``pool_batches`` batches of
``batch`` token sequences of length ``seq``. Each sequence has a class, drawn
uniformly from the configuration's ``num_classes``; a class's block of
``vocab // num_classes`` token ids is ``e^skew`` times as likely as any other
id, so even a random backbone's pooled embeddings separate the classes (the
draw of ``repro.data.synthetic.token_classification``, vectorised). A pool
holds many times the batches a window folds today: no client folds its data
twice, and a batch folded again repeats its float32 rounding, so the fold's
error would grow with the count of batches, not its square root. Before
them the client has folded ``d`` rows of earlier data: standard normal
feature rows with uniform labels, in blocks of ``batch``.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, fixed by the run's seed."""
    return np.random.default_rng([int(seed) & (2**64 - 1),
                                  int.from_bytes(stream.encode(), "little")])


def class_biased_tokens(r: np.random.Generator, labels: np.ndarray, seq: int,
                        vocab: int, num_classes: int, skew: float) -> np.ndarray:
    """Token ids ``labels.shape + (seq,)``: each id is drawn from the
    label's block with probability ``block·e^skew / (block·e^skew + vocab −
    block)``, uniformly inside the block, else uniformly outside it."""
    block = vocab // num_classes
    w_in = block * np.exp(skew)
    p_in = w_in / (w_in + vocab - block)
    shape = labels.shape + (seq,)
    lo = (labels * block)[..., None]
    inside = r.random(shape) < p_in
    in_block = lo + r.integers(0, block, shape)
    outside = r.integers(0, vocab - block, shape)
    outside = outside + np.where(outside >= lo, block, 0)
    return np.where(inside, in_block, outside).astype(np.int32)


def local_pool(mix: dict, cfg: dict, seed: int):
    """(tokens (n, batch, seq) int32, labels (n, batch) int32)."""
    r = rng(seed, "local")
    n, b = mix["pool_batches"], mix["batch"]
    labels = r.integers(0, cfg["num_classes"], (n, b)).astype(np.int32)
    tokens = class_biased_tokens(r, labels, mix["seq"], cfg["vocab_size"],
                                 cfg["num_classes"], mix["skew"])
    return tokens, labels


def prefold(mix: dict, cfg: dict, seed: int):
    """(rows (n, batch, d) float32, labels (n, batch) int32): the ``d``
    rows folded before the window, rounded up to whole blocks of
    ``batch``, so that the window folds on the client's steady path."""
    r = rng(seed, "prefold")
    b, d = mix["batch"], cfg["d_model"]
    n = -(-d // b)
    rows = r.standard_normal((n, b, d), dtype=np.float32)
    return rows, r.integers(0, cfg["num_classes"], (n, b)).astype(np.int32)


def check_rows(n_batches: int, batch: int, total: int, seed: int) -> list:
    """Which rows the forward check compares, drawn from the seed: the same
    number from every one of ``n_batches`` batches, at least ``total`` in
    all (or every row), as sorted (batch, row) pairs."""
    per = min(batch, -(-total // max(n_batches, 1)))
    r = rng(seed, "check")
    return [(b, int(i)) for b in range(n_batches)
            for i in np.sort(r.choice(batch, per, replace=False))]
