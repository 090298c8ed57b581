"""The traffic generator: turns a traffic file into a stream of calls.

A traffic file (``bench/traffic/<name>.json``) is data only; this one
generator reads every one.  Its keys:

* ``graphs``: the graph classes (``num_nodes``, ``avg_degree``) the pool
  holds, in turn; without it, the configuration's ``graph``.
* ``hot_per_call``: graphs that every call repeats (pool entries
  ``0 .. hot - 1``).
* ``pool``, ``fresh_per_call``: each call also takes ``fresh_per_call``
  graphs from the next ``pool`` entries, picked by ``pick``: ``cycle``
  (in order, round and round; the default) or ``zipf`` (drawn with
  probability proportional to ``1 / rank ** zipf_s``, repeats allowed).
* ``shuffle``: send a call's graphs in an order drawn from the run's seed.
* ``arrival``: ``closed`` (the default: the next call starts when the
  last returns), or ``poisson`` / ``uniform`` at ``calls_per_s`` (an open
  loop: calls arrive on a schedule, wait their turn, and a request's
  latency runs from its call's arrival).
* ``warm_calls``: calls sent in set-up before the window (default 1), the
  first of the stream.
* ``base_seed``: the graphs, the Zipf draws and the arrival times, the
  same for every run of a cell; ``why``: a line for the reader.

The run's seed reorders each graph (``graphgen.generate_pool``) and
shuffles the calls, so every seed gets the same sizes, the same work, the
same repeats and the same arrivals, in other arrays.
"""
from __future__ import annotations

from typing import Iterator, List, NamedTuple, Optional

import numpy as np

KNOWN_KEYS = {"why", "graphs", "hot_per_call", "pool", "fresh_per_call",
              "pick", "zipf_s", "shuffle", "arrival", "calls_per_s",
              "warm_calls", "base_seed"}
PICKS = ("cycle", "zipf")
ARRIVALS = ("closed", "poisson", "uniform")
SCHEDULE_KEY = 0x5C4ED  # keeps the schedule's draws apart from the graphs'


class Planned(NamedTuple):
    """One call: when it arrives (seconds after the first call's arrival;
    None in a closed loop) and the pool indices of its graphs."""
    at: Optional[float]
    graphs: List[int]


def validate(traffic: dict) -> None:
    unknown = set(traffic) - KNOWN_KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    pick = traffic.get("pick", "cycle")
    arrival = traffic.get("arrival", "closed")
    if pick not in PICKS:
        raise ValueError(f"pick must be one of {PICKS}")
    if arrival not in ARRIVALS:
        raise ValueError(f"arrival must be one of {ARRIVALS}")
    if (pick == "zipf") != ("zipf_s" in traffic):
        raise ValueError("zipf_s goes with pick zipf, and only with it")
    if (arrival == "closed") == ("calls_per_s" in traffic):
        raise ValueError("calls_per_s goes with an open arrival, and only "
                         "with it")
    if arrival != "closed" and not traffic["calls_per_s"] > 0:
        raise ValueError("calls_per_s must be positive")
    if not 0 < traffic["fresh_per_call"] + traffic.get("hot_per_call", 0):
        raise ValueError("a call needs at least one graph")
    if pick == "cycle" and not traffic["fresh_per_call"] <= traffic["pool"]:
        raise ValueError("fresh_per_call must be at most pool, so that a "
                         "call's cycled graphs are distinct")
    if traffic["pool"] < 1 and traffic["fresh_per_call"]:
        raise ValueError("fresh graphs need a pool")
    if traffic.get("warm_calls", 1) < 1:
        raise ValueError("set-up sends at least one call")
    for g in traffic.get("graphs", [{"num_nodes": 2, "avg_degree": 1}]):
        if set(g) - {"num_nodes", "avg_degree"} or g["num_nodes"] < 2:
            raise ValueError(f"bad graph class {g}")


def pool_size(traffic: dict) -> int:
    return traffic.get("hot_per_call", 0) + traffic["pool"]


def graph_classes(traffic: dict, config: dict) -> List[dict]:
    """Pool entry ``i`` is of class ``classes[i % len(classes)]``."""
    return traffic.get("graphs") or [config["graph"]]


def warm_calls(traffic: dict) -> int:
    return traffic.get("warm_calls", 1)


def calls(traffic: dict, seed) -> Iterator[Planned]:
    """The calls in order, without end; set-up takes the first
    ``warm_calls``, and the window's arrival times count from the one after
    them."""
    hot, fresh, pool = (traffic.get("hot_per_call", 0),
                        traffic["fresh_per_call"], traffic["pool"])
    pick, arrival = (traffic.get("pick", "cycle"),
                     traffic.get("arrival", "closed"))
    schedule = np.random.default_rng([traffic["base_seed"], SCHEDULE_KEY])
    order = np.random.default_rng(seed)
    if pick == "zipf":
        weight = 1.0 / np.arange(1, pool + 1) ** traffic["zipf_s"]
        popularity = weight / weight.sum()
        ranked = schedule.permutation(pool)
    k, t = 0, 0.0
    while True:
        if pick == "cycle":
            picked = [(k + j) % pool for j in range(fresh)]
            k += fresh
        else:
            picked = ranked[schedule.choice(pool, size=fresh,
                                            p=popularity)].tolist()
        ids = list(range(hot)) + [hot + i for i in picked]
        if traffic.get("shuffle"):
            order.shuffle(ids)
        if arrival == "closed":
            yield Planned(None, ids)
            continue
        yield Planned(t, ids)
        rate = traffic["calls_per_s"]
        t += (schedule.exponential(1.0 / rate) if arrival == "poisson"
              else 1.0 / rate)
