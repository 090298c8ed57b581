"""The traffic generator: every mix is a data file read by one generator."""
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

ROOT = Path(__file__).resolve().parents[2]
CYCLE = {"hot_per_call": 2, "fresh_per_call": 3, "pool": 5,
         "base_seed": 11}


def take(traffic, n, seed=2 ** 31 + 3):
    loadgen.validate(traffic)
    return list(itertools.islice(loadgen.calls(traffic, seed), n))


@pytest.mark.parametrize("path", sorted(
    (ROOT / "bench" / "traffic").glob("*.json")), ids=lambda p: p.stem)
def test_every_traffic_file_is_valid(path):
    loadgen.validate(json.loads(path.read_text()))


def test_closed_cycle_repeats_hot_and_cycles_fresh():
    calls = take(CYCLE, 3)
    assert all(c.at is None for c in calls)
    assert [c.graphs for c in calls] == [[0, 1, 2, 3, 4], [0, 1, 5, 6, 2],
                                         [0, 1, 3, 4, 5]]


def test_shuffle_orders_each_call_by_the_seed():
    t = dict(CYCLE, shuffle=True)
    a, b, c = take(t, 4, 1), take(t, 4, 1), take(t, 4, 2)
    assert [x.graphs for x in a] == [x.graphs for x in b]
    assert [sorted(x.graphs) for x in a] == [sorted(x.graphs) for x in c]
    assert [x.graphs for x in a] != [x.graphs for x in c]


@pytest.mark.parametrize("arrival", ["poisson", "uniform"])
def test_open_arrivals_keep_their_rate_and_ignore_the_run_seed(arrival):
    t = dict(CYCLE, arrival=arrival, calls_per_s=50.0)
    calls = take(t, 2001)
    at = np.array([c.at for c in calls])
    assert at[0] == 0 and np.all(np.diff(at) >= 0)
    assert at[-1] / 2000 == pytest.approx(1 / 50.0, rel=0.1)
    assert [c.at for c in take(t, 50, 5)] == list(at[:50])
    if arrival == "uniform":
        np.testing.assert_allclose(np.diff(at), 1 / 50.0)


def test_zipf_pick_is_skewed_and_the_same_for_every_seed():
    t = {"fresh_per_call": 8, "pool": 100, "pick": "zipf", "zipf_s": 0.99,
         "base_seed": 4}
    calls = take(t, 500)
    assert calls == take(t, 500, seed=9)
    counts = np.bincount([i for c in calls for i in c.graphs], minlength=100)
    top = np.sort(counts)[::-1]
    assert top[0] > 10 * np.median(top) and top[:10].sum() > 0.35 * 4000


def test_graph_classes_default_to_the_configuration():
    config = {"graph": {"num_nodes": 10, "avg_degree": 3}}
    assert loadgen.graph_classes(CYCLE, config) == [config["graph"]]
    mixed = dict(CYCLE, graphs=[{"num_nodes": 10, "avg_degree": 3},
                                {"num_nodes": 10, "avg_degree": 9}])
    loadgen.validate(mixed)
    assert loadgen.graph_classes(mixed, config) == mixed["graphs"]
    assert loadgen.pool_size(mixed) == 7
    assert loadgen.warm_calls(mixed) == 1


@pytest.mark.parametrize("bad", [
    dict(CYCLE, loop="closed"),
    dict(CYCLE, pick="random"),
    dict(CYCLE, pick="zipf"),
    dict(CYCLE, zipf_s=1.0),
    dict(CYCLE, arrival="poisson"),
    dict(CYCLE, calls_per_s=3.0),
    dict(CYCLE, arrival="uniform", calls_per_s=0),
    dict(CYCLE, fresh_per_call=6),
    dict(CYCLE, hot_per_call=0, fresh_per_call=0),
    dict(CYCLE, warm_calls=0),
    dict(CYCLE, graphs=[{"num_nodes": 10, "avg_degree": 3, "skew": 1}]),
], ids=lambda t: ",".join(sorted(set(t) ^ set(CYCLE))) or "changed")
def test_validate_refuses_what_it_cannot_generate(bad):
    with pytest.raises(ValueError):
        loadgen.validate(bad)
