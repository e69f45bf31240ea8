"""The seed decides content and order, never the amount of work."""
import collections
import glob
import json
import os

import numpy as np
import pytest

from benchmark import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
FILES = sorted(glob.glob(os.path.join(HERE, "..", "traffic", "*.json")))
SEEDS = (1, 2147483659, 4000000007)


def _spec(path):
    with open(path) as f:
        return json.load(f)


def _train_shape(feed):
    """Multiset of (real length, labels) over the rows of every batch."""
    out = collections.Counter()
    for step in range(feed["ids"].shape[0]):
        for row in range(feed["ids"].shape[1]):
            real = (feed["ids"].shape[2] if feed["mask"] is None
                    else int(feed["mask"][step, row].sum()))
            out[(real, int((feed["labels"][step, row] != -100).sum()))] += 1
    return out


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_same_work_for_any_seed_same_bytes_for_same_seed(path):
    spec = _spec(path)
    assert spec["kind"] == "train_batches"
    rows = min(spec["batch_per_chip"], 16)
    spec = dict(spec, steps_per_reading=2)
    feeds = [traffic.train_feed(spec, 1000, rows, s, 0) for s in SEEDS]
    again = traffic.train_feed(spec, 1000, rows, SEEDS[0], 0)
    assert all(_train_shape(f) == _train_shape(feeds[0]) for f in feeds)
    assert not np.array_equal(feeds[0]["ids"], feeds[1]["ids"])
    for key in ("ids", "labels"):
        assert feeds[0][key].tobytes() == again[key].tobytes()
    other = traffic.train_feed(spec, 1000, rows, SEEDS[0], 1)
    assert not np.array_equal(feeds[0]["ids"], other["ids"])
    if spec.get("padded"):
        assert all(spec["length_lo"] <= k[0] <= spec["length_hi"]
                   for k in _train_shape(feeds[0]))


def test_huge_seed_is_taken():
    spec = _spec(FILES[0])
    assert traffic.seed32(2 ** 31 + 12345) < 2 ** 32
    traffic.train_feed(dict(spec, steps_per_reading=1), 100, 4,
                       2 ** 31 + 12345, 0)
