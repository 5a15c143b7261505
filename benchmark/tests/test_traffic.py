import json
import os

import numpy as np
import pytest

from benchmark import traffic as T

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")
CFG = {"vocab_size": 30522, "type_vocab_size": 2}
SEEDS = (0, 7, 3_000_000_019)


def load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def test_quantile_lengths_are_the_files_and_stay_in_range():
    spec = {"dist": "lognormal", "median": 64, "sigma": 0.6, "min": 16,
            "max": 128}
    a = T.quantile_lengths(spec, 256)
    assert a.min() >= 16 and a.max() <= 128 and len(a) == 256
    assert abs(np.median(a) - 64) <= 1
    assert (np.diff(a) >= 0).all()
    with pytest.raises(ValueError):
        T.quantile_lengths(dict(spec, dist="uniform"), 8)


def test_closed_loop_every_seed_offers_the_same_multiset():
    tr = load("chat_closed_c128")
    want = T.closed_loop_multiset(tr)
    orders = []
    for seed in SEEDS:
        reqs = T.closed_loop_requests(tr, CFG, seed, 3 * tr["pairs"])
        for k in range(3):      # each cycle is the whole multiset
            cyc = reqs[k * tr["pairs"]:(k + 1) * tr["pairs"]]
            assert T.length_multiset(cyc) == want
        orders.append([r.prompt.size for r in reqs[:32]])
        assert all(r.prompt.max() < CFG["vocab_size"] for r in reqs[:8])
    assert orders[0] != orders[1]           # the seed changes the order
    again = T.closed_loop_requests(tr, CFG, SEEDS[2], 40)
    first = T.closed_loop_requests(tr, CFG, SEEDS[2], 40)
    assert all((a.prompt == b.prompt).all() for a, b in zip(again, first))


def test_stream_batches_have_the_feed_layout():
    tr = load("stream_b96_s128")
    a = T.stream_batches(tr, CFG, 5)
    b = T.stream_batches(tr, CFG, 5)
    assert len(a) == tr["distinct_batches"]
    assert a[0]["src_ids"].shape == (96, 128)
    assert a[0]["mask_label"].shape == (96 * 20, 1)
    assert (a[3]["src_ids"] == b[3]["src_ids"]).all()
    assert not (a[0]["src_ids"] == a[1]["src_ids"]).all()


def test_stride_pairing_refuses_a_stride_that_repeats():
    with pytest.raises(ValueError):
        T.stride_pairing(256, 2)
