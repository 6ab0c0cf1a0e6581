"""Traffic: the seed orders the work and never changes its sizes."""
import glob
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import manifest as mf  # noqa: E402
from benchmark import traffic  # noqa: E402

M = mf.load_manifest()
MIXES = sorted(glob.glob(os.path.join(mf.HERE, "traffic", "*.json")))


def test_train_batches_repeat_and_rows_differ():
    a = traffic.train_batch(2 ** 31 + 9, 4, 8, 64, 50304, "next")
    b = traffic.train_batch(2 ** 31 + 9, 4, 8, 64, 50304, "next")
    c = traffic.train_batch(2 ** 31 + 9, 5, 8, 64, 50304, "next")
    assert np.array_equal(a[0], b[0]) and not np.array_equal(a[0], c[0])
    assert len({tuple(r) for r in a[0]}) == 8
    assert np.array_equal(a[1][:, :-1], a[0][:, 1:])
    r = traffic.train_batch(1, 0, 4, 16, 100, "random")
    assert not np.array_equal(r[1][:, :-1], r[0][:, 1:])


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_every_seed_gets_the_same_sizes_and_other_tokens(seed):
    a = traffic.train_batch(seed, 0, 8, 64, 50304, "next")
    b = traffic.train_batch(seed + 1, 0, 8, 64, 50304, "next")
    assert a[0].shape == b[0].shape == (8, 64) and a[0].dtype == np.int32
    assert not np.array_equal(a[0], b[0])
    assert 0 <= a[0].min() and a[0].max() < 50304


def test_an_unknown_labels_rule_is_an_error():
    with pytest.raises(ValueError):
        traffic.train_batch(1, 0, 4, 16, 100, "masked")


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_every_mix_file_is_a_whole_training_mix(path):
    mix = mf._json(path)
    assert mix["kind"] == "train"
    chips = {c["traffic"]: c["chips"] for c in M["workloads"]}[
        os.path.basename(path)[:-len(".json")]]
    sizes = traffic.train_mix(mix, chips)
    assert set(mix) == {"kind", "batch", "seq", "labels"}
    assert sizes["batch"] % chips == 0
    assert sizes["labels"] in ("next", "random")


def test_the_global_batch_has_to_divide_over_the_chips():
    mix = mf._json(os.path.join(mf.HERE, "traffic", "train-s1024-dp4.json"))
    assert traffic.train_mix(mix, 4)["batch"] == 32
    with pytest.raises(ValueError):
        traffic.train_mix({**mix, "batch": 30}, 4)
