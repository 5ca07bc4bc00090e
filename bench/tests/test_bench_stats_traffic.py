"""The traffic generator: the file fixes the work, the seed the data."""
import json

import numpy as np
import pytest

from bench.lib import traffic
from bench.tests.conftest import ROOT

BIG_SEED = 2 ** 31 + 2 ** 40 + 12345


def test_op_round_from_the_traffic_file():
    tr = json.loads((ROOT / "bench/traffic/small-seg.json").read_text())
    assert traffic.op_calls(tr) == [("reduce", 16), ("scan", 16),
                                    ("reduce", 256), ("scan", 256)]


@pytest.mark.parametrize("name", ["small-seg", "large-seg"])
def test_every_round_covers_the_same_elements(name):
    tr = json.loads((ROOT / f"bench/traffic/{name}.json").read_text())
    for _, seg in traffic.op_calls(tr):
        assert tr["total_elements"] % seg == 0
    assert tr["total_elements"] % tr["rehearsal"]["total_elements"] == 0


def test_generators_reproducible_from_a_large_seed():
    a = traffic.rng_for(BIG_SEED, 5).integers(2 ** 31, size=8)
    b = traffic.rng_for(BIG_SEED, 5).integers(2 ** 31, size=8)
    assert np.array_equal(a, b)


def test_streams_and_seeds_draw_apart():
    draw = [traffic.rng_for(s, k).integers(2 ** 31, size=8)
            for s, k in ((BIG_SEED, 5), (BIG_SEED, 6), (BIG_SEED + 1, 5))]
    assert not np.array_equal(draw[0], draw[1])
    assert not np.array_equal(draw[0], draw[2])
