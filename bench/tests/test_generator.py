"""Schedules come from the seed alone, and every seed gets the same
amount of work."""

import numpy as np
import pytest

from bench import generator

OPEN = {"loop": "open", "scenario": "poisson", "rate_fps": 300}


@pytest.mark.parametrize("traffic", [OPEN, {"loop": "closed", "clients": 32}])
def test_same_seed_same_plan(traffic):
    a = generator.make_plan(traffic, seed=2**31 + 9, seconds=10, pool=64)
    b = generator.make_plan(traffic, seed=2**31 + 9, seconds=10, pool=64)
    c = generator.make_plan(traffic, seed=2**31 + 10, seconds=10, pool=64)
    assert np.array_equal(a.frame_idx, b.frame_idx)
    assert not np.array_equal(a.frame_idx, c.frame_idx)
    assert a.record == b.record and a.clients == b.clients
    assert a.frame_idx.min() >= 0 and a.frame_idx.max() < 64
    if a.loop == "open":
        assert np.array_equal(a.offsets, b.offsets)
    if a.loop == "open":
        assert not np.array_equal(a.offsets, c.offsets)


def test_every_seed_sends_the_same_number_inside_the_window():
    for seed in range(5):
        p = generator.make_plan(OPEN, seed=seed, seconds=10, pool=64)
        assert len(p.offsets) == len(p.frame_idx) == 3000
        assert p.offsets[0] == 0 and p.offsets[-1] < 10
        assert np.all(np.diff(p.offsets) >= 0)


@pytest.mark.parametrize("traffic", [
    dict(OPEN, burst=2), dict(OPEN, scenario="pareto"), {"loop": "sideways"}])
def test_unknown_parameters_are_refused(traffic):
    with pytest.raises(ValueError):
        generator.make_plan(traffic, seed=0, seconds=1, pool=4)


def test_pacing_report():
    r = generator.pacing_report(np.array([0.0, 1.0]), np.array([0.001, 1.003]))
    assert r["arrivals"] == 2
    assert r["lag_ms_max"] == pytest.approx(3.0)
