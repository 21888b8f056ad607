"""The one traffic generator: turns a mix's data file into requests.

A mix (``bench/traffic/<name>.json``) is a closed loop: ``outstanding``
requests are kept in flight, and the next is sent when one completes.  The
list it sends from holds ``population`` requests, more than a window can
send.

``seeds`` says which vertices requests start from: distinct vertices drawn
uniformly from those whose degree lies in ``[min_degree, max_degree]``.

``mix`` lists the request kinds with their ``share``; each kind is the
keyword arguments of a ``ClusterRequest`` besides the seed.  Requests are
dealt in exact proportion to the shares.

``population_seed`` fixes the graph and the list of requests for every
run: a run's ``--seed`` only deals them in its own order within blocks of
``outstanding`` (a window sends a prefix of the list, so every run sends
nearly the same requests and does nearly the same work).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.serve import ClusterRequest


@dataclasses.dataclass
class Stream:
    """Requests for one run: ``window`` are timed, ``warm`` warm up."""
    window: list
    warm: list


def _kinds(mix: list, count: int, rng) -> list:
    """``count`` request kinds in exact proportion to their shares."""
    shares = np.array([k["share"] for k in mix], float)
    counts = np.floor(shares / shares.sum() * count).astype(int)
    counts[np.argsort(-shares)[: count - counts.sum()]] += 1
    kinds = [k for k, c in zip(mix, counts) for _ in range(c)]
    return [kinds[i] for i in rng.permutation(count)]


def request(kind: dict, seed: int) -> ClusterRequest:
    """The request of mix entry ``kind`` from vertex ``seed``."""
    return ClusterRequest(seed=int(seed),
                          **{k: v for k, v in kind.items() if k != "share"})


def make_stream(traffic: dict, deg: np.ndarray, seed: int) -> Stream:
    """The requests of one run, dealt in the order ``seed`` draws."""
    rng = np.random.default_rng(traffic["population_seed"])
    count = traffic["population"]
    warm_count = traffic["warm_requests"]
    sel = traffic["seeds"]
    pool = np.flatnonzero((deg >= sel["min_degree"])
                          & (deg <= sel["max_degree"]))
    picked = rng.choice(pool, size=count + warm_count, replace=False)
    window = [request(k, s) for k, s in
              zip(_kinds(traffic["mix"], count, rng), picked[:count])]
    warm = [request(k, s) for k, s in
            zip(_kinds(traffic["mix"], warm_count, rng), picked[count:])]
    order = np.random.default_rng(seed)
    block = traffic["outstanding"]
    deal = np.concatenate([i + order.permutation(min(block, count - i))
                           for i in range(0, count, block)])
    return Stream([window[i] for i in deal], warm)
