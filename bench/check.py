"""Decides ``correct``: the served answers against the plain reference.

Each number below is the widest gap over the answers checked in a run:

* ``answer_gap`` — the reported conductance against the conductance of the
  reported cluster, recomputed exactly on the host; 1 where the cluster is
  no set of distinct vertices or its size or volume is misreported;
* ``phi_gap`` — the cluster's exact conductance against the reference's
  best sweep cut, relative;
* ``support_gap`` — vertices the diffusion reached, against the reference;
* ``pushes_gap`` — pushes the diffusion made, against the reference.

A mix's ``limits`` name the numbers its cells compare, each with its limit.

``unanswered`` counts admitted requests that never came back, which the
limit 0 allows none of.  A late answer is late, not wrong: every request
the window sent is waited for up to a minute past its close.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

from bench.reference import HostGraph, Workspace, conductance_of

NUMBERS = ("answer_gap", "phi_gap", "support_gap", "pushes_gap")


class Served(NamedTuple):
    """The fields of a served answer that :func:`judge` reads."""
    request: object
    conductance: float
    cluster: np.ndarray
    size: int
    volume: int
    support: int
    pushes: int


def served(req, answer, g: HostGraph) -> Served:
    """A reference :class:`~bench.reference.Answer` to ``req`` as if it
    were served."""
    return Served(req, answer.conductance, answer.cluster,
                  len(answer.cluster), int(g.deg[answer.cluster].sum()),
                  answer.support, answer.pushes)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)


def gaps(answer, ref, g: HostGraph) -> dict:
    """The compared numbers for one answer against its reference."""
    own = conductance_of(g, answer.cluster)
    if (own is None or own[1] != answer.volume
            or answer.size != len(answer.cluster)):
        bad = 1.0
        phi = np.inf
    else:
        phi = own[0]
        bad = _rel(float(answer.conductance), phi)
    return {"answer_gap": bad,
            "phi_gap": _rel(phi, ref.conductance) if np.isfinite(phi) else 1.0,
            "support_gap": _rel(answer.support, ref.support),
            "pushes_gap": _rel(answer.pushes, ref.pushes)}


@dataclasses.dataclass
class Verdict:
    correct: bool
    failed: int
    checks: dict            # name -> {"value": reading, "limit": limit}


def sample(answered: list, count: int, seed: int) -> list:
    """Indices of the answers to check: ``count`` drawn from the seed, and
    the answer whose diffusion reached the most vertices."""
    if not answered:
        return []
    rng = np.random.default_rng([seed, 0xC4EC])
    picks = set(rng.choice(len(answered), min(count, len(answered)),
                           replace=False).tolist())
    picks.add(int(np.argmax([r.result.support for r in answered])))
    return sorted(picks)


def judge(records: list, g: HostGraph, traffic: dict, seed: int) -> Verdict:
    limits = traffic["limits"]
    answered = [r for r in records if r.result is not None]
    unanswered = sum(1 for r in records
                     if r.result is None and not r.refused)
    refused = sum(1 for r in records if r.refused)
    numbers = [k for k in NUMBERS if k in limits]
    worst = dict.fromkeys(numbers, 0.0)
    wrong = 0
    ws = Workspace(g, "float64")
    for i in sample(answered, traffic["check_sample"], seed):
        res = answered[i].result
        got = gaps(res, ws.answer(res.request), g)
        wrong += any(got[k] > limits[k] for k in numbers)
        for k in numbers:
            worst[k] = max(worst[k], got[k])
    checks = {k: {"value": worst[k], "limit": limits[k]} for k in numbers}
    checks["unanswered"] = {"value": unanswered, "limit": 0}
    correct = bool(answered) and all(c["value"] <= c["limit"]
                                     for c in checks.values())
    return Verdict(correct, refused + unanswered + wrong, checks)
