"""Token streams from a seed: training shards and prompts.

Zipf-distributed ids (exponent 1.2, clipped into the vocabulary), the shape
the program's own synthetic shards have: a non-flat unigram distribution, so
that the loss moves as real text's does; every row differs.  Shards are
``.npy`` files of uint16 with the split in the name, the on-disk format of the
source's loader.
"""

from __future__ import annotations

import os

import numpy as np


def zipf_tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return (rng.zipf(1.2, size=n) - 1).clip(max=vocab - 1).astype(np.uint16)


def write_shards(data_dir: str, seed: int, vocab: int, train_tokens: int,
                 val_tokens: int) -> dict:
    """One train and one val shard from the seed; returns their paths."""
    os.makedirs(data_dir, exist_ok=True)
    for f in os.listdir(data_dir):  # another seed's shards
        if f.endswith(".npy"):
            os.remove(os.path.join(data_dir, f))
    paths = {}
    for i, (split, n) in enumerate((("train", train_tokens), ("val", val_tokens))):
        rng = np.random.default_rng([int(seed), i])
        paths[split] = os.path.join(data_dir, f"bench_{split}_000000.npy")
        np.save(paths[split], zipf_tokens(rng, n, vocab))
    return paths


def step_batches(shard_path: str, steps: int, accum: int, rows: int,
                 seq_len: int):
    """The (x, y) a sequential next-token loader feeds the first ``steps``
    steps from one shard: windows of rows*seq_len+1 tokens, one after another.
    x, y (accum, rows, seq_len) int32."""
    tokens = np.load(shard_path).astype(np.int32)
    span = rows * seq_len
    out = []
    for k in range(steps):
        xs, ys = [], []
        for j in range(accum):
            pos = (k * accum + j) * span
            buf = tokens[pos:pos + span + 1]
            xs.append(buf[:-1].reshape(rows, seq_len))
            ys.append(buf[1:].reshape(rows, seq_len))
        out.append((np.stack(xs), np.stack(ys)))
    return out
