"""The one traffic generator: turns a traffic file's parameters and a
seed into the calls of one run.

The file fixes the work (the round of calls and their sizes); the seed
draws only the data, so two seeds do the same work in the same order and
a run's spread comes from the system, not from the draw.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose; seeds may exceed 32 bits."""
    return np.random.default_rng([int(seed) % (2 ** 64), stream])


def op_calls(traffic: dict):
    """The ops cell's round: ``[(op, seg), ...]`` issued in this order,
    again and again, for the whole window."""
    return [(c["op"], int(c["seg"])) for c in traffic["calls"]]
