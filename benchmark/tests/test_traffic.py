"""The traffic generators: the same seed gives the same inputs; another seed
gives the same sizes and arrivals with other tokens."""

import json
import os

import numpy as np
import pytest

from benchmark.traffic import requests as traffic
from benchmark.traffic import tokens
from conftest import CHECKOUT


def _mix(cell):
    return json.load(open(os.path.join(CHECKOUT, "benchmark", "workloads",
                                       cell + ".json")))["mix"]


def test_open_loop_is_a_function_of_the_seed():
    mix = _mix("serve-mamba2-280m-chat")
    a = traffic.open_loop(mix, 2**31 + 11, 30.0)
    b = traffic.open_loop(mix, 2**31 + 11, 30.0)
    assert a == b
    assert np.array_equal(a[3].prompt(50304), b[3].prompt(50304))
    assert all(0 <= s.due_s < 30.0 for s in a)
    assert [s.due_s for s in a] == sorted(s.due_s for s in a)


def test_a_seed_changes_the_tokens_and_never_a_size_or_an_arrival():
    mix = _mix("serve-mamba2-280m-chat")
    a, b = (traffic.open_loop(mix, s, 30.0) for s in (1, 2**31 + 2))
    what = lambda xs: [(s.prompt_len, s.max_new, s.greedy, s.due_s) for s in xs]
    assert what(a) == what(b)
    assert not np.array_equal(a[0].prompt(50304)[:16], b[0].prompt(50304)[:16])
    p = mix["prompt"]
    assert all(p["min"] <= s.prompt_len <= p["max"] for s in a)
    # the rate is the file's number
    assert len(a) / 30.0 == pytest.approx(mix["arrivals"]["rate_per_s"], rel=0.25)


def test_closed_loop_population():
    mix = _mix("serve-hybrid-280m-longdoc")
    a, b = traffic.closed_loop(mix, 5), traffic.closed_loop(mix, 6)
    assert len(a) == mix["population"]
    assert [s.prompt_len for s in a] == [s.prompt_len for s in b]
    assert sum(s.greedy for s in a) == round(mix["greedy_share"] * len(a))
    assert max(s.prompt_len + s.max_new for s in a) <= 8192


def test_gamma_arrivals_are_bursty():
    rng = np.random.default_rng(0)
    gaps = traffic.arrival_gaps(rng, {"process": "gamma", "cv": 2.0,
                                      "rate_per_s": 50.0}, 400.0)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(2.0, rel=0.1)
    assert np.cumsum(gaps)[-1] < 400.0


def test_shards_and_the_rows_a_loader_feeds(tmp_path):
    from mamba_distributed_tpu.data import ShardedTokenLoader

    paths = tokens.write_shards(str(tmp_path), 2**31 + 3, 4096, 40000, 9000)
    again = np.load(paths["train"]).copy()
    tokens.write_shards(str(tmp_path), 2**31 + 3, 4096, 40000, 9000)
    assert np.array_equal(again, np.load(paths["train"]))
    assert again.dtype == np.uint16 and again.max() < 4096
    loader = ShardedTokenLoader(B=4, T=64, data_dir=str(tmp_path), split="train",
                                master_process=False, prefetch=False)
    batches = tokens.step_batches(paths["train"], steps=3, accum=2, rows=4, seq_len=64)
    for x, y in batches:
        for j in range(2):
            lx, ly = loader.next_batch()
            assert np.array_equal(lx, x[j]) and np.array_equal(ly, y[j])
    loader.close()
    rows = np.concatenate([x.reshape(-1, 64) for x, _ in batches])
    assert len({r.tobytes() for r in rows}) == len(rows)  # rows all differ
