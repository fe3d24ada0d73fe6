"""The one general traffic generator: a closed loop of query parameters
read from a mix's data file.

A mix (``traffic/<name>.json``) holds::

    {"query": "q6", "loop": "closed", "clients": 1, "trace_queries": 3,
     "parameters": {"year": {"int_range": [1993, 1997]},
                    "discount": {"hundredths_range": [2, 9]},
                    "quantity": {"values": [24, 25]}}}

Each parameter is a finite list of values.  The stream walks the cross
product of the lists in an order shuffled by the seed, and starts over with
a new shuffle when it runs out: every seed sends the same set of parameter
sets in another order, and neighbouring queries do not share all their
literals.
"""

from __future__ import annotations

import itertools
from typing import Iterator, List

import numpy as np


def parameter_values(rule: dict) -> List:
    """The finite list of values one parameter's rule stands for."""
    if len(rule) != 1:
        raise ValueError(f"a parameter rule has one key, got {rule}")
    (kind, arg), = rule.items()
    if kind == "values":
        return list(arg)
    if kind == "int_range":
        lo, hi = arg
        return list(range(int(lo), int(hi) + 1))
    if kind == "hundredths_range":
        # decimal literals with two places: 0.02, 0.03, ... as the
        # nearest doubles, never as sums of 0.01
        lo, hi = arg
        return [h / 100.0 for h in range(int(lo), int(hi) + 1)]
    raise ValueError(f"unknown parameter rule {kind!r}")


def parameter_sets(mix: dict) -> List[dict]:
    """Every parameter set of the mix, in a fixed order."""
    names = sorted(mix["parameters"])
    lists = [parameter_values(mix["parameters"][n]) for n in names]
    return [dict(zip(names, combo)) for combo in itertools.product(*lists)]


def parameter_stream(mix: dict, seed: int) -> Iterator[dict]:
    """Endless parameter sets for one client, drawn from ``seed``."""
    if mix.get("loop") != "closed" or int(mix.get("clients", 1)) != 1:
        raise ValueError(
            "this generator sends a closed loop of one client; "
            f"the mix asks for {mix.get('loop')!r} x {mix.get('clients')}")
    sets = parameter_sets(mix)
    rng = np.random.default_rng([int(seed), 0x7AF])
    while True:
        for i in rng.permutation(len(sets)):
            yield sets[int(i)]
