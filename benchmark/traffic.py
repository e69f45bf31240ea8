"""The one general traffic generator: a traffic file of parameters in,
the work of a run out.

What a run does is fixed by the file: the multiset of shapes of a training
batch (its rows' real lengths and label counts). `--seed` decides content
and order only: token ids, label positions, which row takes which length.
Two seeds therefore do the same work. A mix of another `kind` (a serving
schedule) brings its generator with the PR that ships its first cell.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        return json.load(f)


def seed32(seed: int, salt: int = 0) -> int:
    # --seed may exceed 2**31; numpy's RandomState takes 32 unsigned bits
    return (int(seed) * 2654435761 + salt * 40503 + 12345) % (2 ** 32)


def train_lengths(spec: dict, rows: int) -> np.ndarray:
    """The fixed multiset of real lengths of one global batch of `rows`."""
    if not spec.get("padded"):
        return np.full((rows,), spec["seq"], np.int64)
    return np.rint(np.linspace(spec["length_lo"], spec["length_hi"],
                               rows)).astype(np.int64)


def train_labels_per_row(spec: dict, lengths: np.ndarray) -> np.ndarray:
    return np.maximum(1, np.rint(spec["label_rate"] * lengths)).astype(
        np.int64)


def train_label_share(spec: dict, rows: int) -> float:
    lengths = train_lengths(spec, rows)
    return float(train_labels_per_row(spec, lengths).sum()) / (
        rows * spec["seq"])


def train_feed(spec: dict, vocab: int, rows: int, seed: int,
               index: int) -> dict:
    """Feed number `index` of a run: `steps_per_reading` batches that all
    differ, as arrays with a leading [k] axis. `ids` [k,B,S], `labels`
    [k,B,S] with -100 where no label, `mask` [k,B,S] float (1 = real) or
    None when the mix is unpadded."""
    k, seq = spec["steps_per_reading"], spec["seq"]
    rng = np.random.RandomState(seed32(seed, 1 + index))
    lengths = train_lengths(spec, rows)
    ids = rng.randint(0, vocab, (k, rows, seq)).astype(np.int64)
    labels = np.full((k, rows, seq), -100, np.int64)
    mask = np.zeros((k, rows, seq), np.float32) if spec.get("padded") else None
    for step in range(k):
        row_len = lengths[rng.permutation(rows)]
        n_lab = train_labels_per_row(spec, row_len)
        for r in range(rows):
            pos = rng.choice(row_len[r], size=n_lab[r], replace=False)
            labels[step, r, pos] = rng.randint(0, vocab, n_lab[r])
            if mask is not None:
                mask[step, r, :row_len[r]] = 1.0
    return {"ids": ids, "labels": labels, "mask": mask}
