"""Causal-LM training batches from the general generator's.

`benchmark/tests/test_traffic.py` holds every file under `traffic/` to the
general generator (`traffic.train_feed`, kind `train_batches`), so a
causal-LM mix is such a file: unpadded rows of `seq` tokens whose ids the
general generator draws from `--seed`. What this file adds is the rule the
file names with `"labels": "next_token"`: every position but a row's last
is labelled with the token that follows it. The work is fixed by the file
(`batch_per_chip` rows of `seq` tokens a step); the seed decides the ids
alone, uniform over the vocabulary rows the configuration holds.
"""
from __future__ import annotations

import numpy as np

from . import traffic

IGNORE = -100


def lm_feed(spec: dict, vocab: int, rows: int, seed: int, index: int) -> dict:
    """Feed number `index` of a run: `steps_per_reading` batches that all
    differ. `ids` [k, B, S] in 0..vocab-1; `labels` [k, B, S]: the next
    token, -100 at a row's last position."""
    if spec.get("labels") != "next_token" or spec.get("padded"):
        raise ValueError("lm_feed wants an unpadded mix with "
                         '"labels": "next_token"')
    ids = traffic.train_feed(spec, vocab, rows, seed, index)["ids"]
    labels = np.concatenate(
        [ids[:, :, 1:], np.full(ids.shape[:2] + (1,), IGNORE, np.int64)],
        axis=2)
    return {"ids": ids, "labels": labels}
