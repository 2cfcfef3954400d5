"""The traffic generator (``bench/traffic.py``): seeded, same work per
seed in another order, lengths inside their clips."""
import json
import pathlib

import numpy as np
import pytest

from bench import traffic

MIXES = ("chat",)


ROOT = pathlib.Path(__file__).resolve().parents[2]


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    a = traffic.make_schedule(mix, 3_000_000_019, 10, 32000)
    b = traffic.make_schedule(mix, 3_000_000_019, 10, 32000)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.max_new, b.max_new)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_work(name):
    mix = _mix(name)
    a = traffic.make_schedule(mix, 1, 10, 32000)
    b = traffic.make_schedule(mix, 2, 10, 32000)
    assert len(a) == len(b)
    assert not np.array_equal(a.due, b.due)
    for seg in ((lambda s: s.due < s.window_start),
                (lambda s: s.due >= s.window_start)):
        ma, mb = seg(a), seg(b)
        assert sorted(a.max_new[ma]) == sorted(b.max_new[mb])
        la = sorted(len(p) for p, m in zip(a.prompts, ma) if m)
        lb = sorted(len(p) for p, m in zip(b.prompts, mb) if m)
        assert la == lb


@pytest.mark.parametrize("name", MIXES)
def test_lengths_clip_and_rate(name):
    mix = _mix(name)
    s = traffic.make_schedule(mix, 5, 20, 32000)
    plen = np.array([len(p) for p in s.prompts])
    assert plen.min() >= mix["prompt_len"]["min"]
    assert plen.max() <= mix["prompt_len"]["max"]
    assert s.max_new.min() >= mix["output_len"]["min"]
    assert s.max_new.max() <= mix["output_len"]["max"]
    window = s.due >= s.window_start
    assert window.sum() == round(mix["rate_rps"] * 20)
    assert s.due[window].max() < s.window_start + 20
    assert np.all(np.diff(s.due[window]) > 0)
    assert all(p.min() >= 1 and p.max() < 32000 for p in s.prompts)


def test_quantiles_worked_by_hand():
    q = traffic.length_quantiles(
        {"median": 100, "sigma": 0.0, "min": 1, "max": 1000}, 4)
    assert list(q) == [100] * 4
    q = traffic.length_quantiles(
        {"median": 100, "sigma": 1.0, "min": 50, "max": 200}, 2)
    # u = 0.25, 0.75: 100 * exp(-+0.6745) = 50.9, 196.3
    assert list(q) == [51, 196]
    g = traffic.gap_quantiles(2.0, 3.0, 3)
    assert g.sum() == pytest.approx(3.0)
    assert np.all(np.diff(g) > 0)


def test_balanced_order_spreads_every_band():
    import numpy as np
    rng = np.random.default_rng(7)
    v = np.arange(20)
    a = traffic.balanced_order(rng, v, bands=4)
    assert sorted(a) == list(v)
    # bands of 5: [0,5) [5,10) [10,15) [15,20); each run of 4 holds one of each
    for t in range(5):
        assert sorted(x // 5 for x in a[4 * t:4 * t + 4]) == [0, 1, 2, 3]
    b = traffic.balanced_order(np.random.default_rng(8), v, bands=4)
    assert not np.array_equal(a, b)
    # a length that does not divide: the last run takes what is left
    c = traffic.balanced_order(rng, np.arange(7), bands=3)
    assert sorted(c) == list(range(7))
    assert sorted(x // 3 for x in c[:3]) == [0, 1, 2]
