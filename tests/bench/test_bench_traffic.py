"""The traffic generator (``bench/traffic.py``): seeded, same work per
seed in another order (or, with ``order_seed``, the same schedule),
lengths inside their clips."""
import hashlib
import json
import pathlib

import numpy as np
import pytest

from bench import traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
# every mix file, so that a mix added later is covered with no edit here
MIXES = tuple(sorted(p.stem for p in (ROOT / "bench" / "traffic")
                     .glob("*.json")))
ORDER_SEED = 18            # laid over a mix that has none of its own


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def _unordered(name):
    """The mix with the run's seed choosing the order."""
    return {k: v for k, v in _mix(name).items() if k != "order_seed"}


def _ordered(name):
    """The mix with a fixed order: its own ``order_seed``, or one laid
    over it."""
    return {"order_seed": ORDER_SEED, **_mix(name)}


def _lengths(s):
    return np.array([len(p) for p in s.prompts])


def _digest(s):
    """sha256 over due, max_new, every prompt (length, then ids) and the
    window's start."""
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(s.due, np.float64).tobytes())
    h.update(np.ascontiguousarray(s.max_new, np.int64).tobytes())
    for p in s.prompts:
        h.update(np.int64(len(p)).tobytes())
        h.update(np.ascontiguousarray(p, np.int32).tobytes())
    h.update(np.float64(s.window_start).tobytes())
    return h.hexdigest()


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
    mix = _unordered(name)
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


@pytest.mark.parametrize("name", MIXES)
def test_order_seed_fixes_the_schedule(name):
    mix = _ordered(name)
    a = traffic.make_schedule(mix, 3_718_000_011, 51, 32000)
    b = traffic.make_schedule(mix, 3_718_000_012, 51, 32000)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.max_new, b.max_new)
    assert np.array_equal(_lengths(a), _lengths(b))
    assert a.window_start == b.window_start
    # the run's seed draws the token ids, and nothing else
    assert not any(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    # another order seed: the same work in another order
    c = traffic.make_schedule({**mix, "order_seed": mix["order_seed"] + 1},
                              3_718_000_011, 51, 32000)
    assert not np.array_equal(a.due, c.due)
    assert sorted(a.max_new) == sorted(c.max_new)
    assert sorted(_lengths(a)) == sorted(_lengths(c))


@pytest.mark.parametrize("scale", (0.5, 1.25))
@pytest.mark.parametrize("name", MIXES)
def test_order_seed_under_the_rate_override(name, scale):
    """The knee sweep's ``rate``: one fixed order at the new rate."""
    mix = _ordered(name)
    rate = scale * mix["rate_rps"]
    a = traffic.make_schedule(mix, 3_718_000_021, 51, 32000, rate=rate)
    b = traffic.make_schedule(mix, 3_718_000_022, 51, 32000, rate=rate)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.max_new, b.max_new)
    assert np.array_equal(_lengths(a), _lengths(b))
    window = a.due >= a.window_start
    assert window.sum() == round(rate * 51)
    assert (~window).sum() == round(rate * mix["warmup_s"])
    assert a.due[window].max() < a.window_start + 51
    plen = _lengths(a)
    assert plen.min() >= mix["prompt_len"]["min"]
    assert plen.max() <= mix["prompt_len"]["max"]
    assert a.max_new.min() >= mix["output_len"]["min"]
    assert a.max_new.max() <= mix["output_len"]["max"]


@pytest.mark.parametrize("name", MIXES)
def test_committed_mix_order(name):
    """A mix file as committed: with ``order_seed`` every seed serves one
    schedule; without it, seeds reorder it."""
    mix = _mix(name)
    a = traffic.make_schedule(mix, 3_718_000_031, 51, 32000)
    b = traffic.make_schedule(mix, 3_718_000_032, 51, 32000)
    assert np.array_equal(a.due, b.due) == ("order_seed" in mix)


# sha256 of chat schedules taken on commit 9c26c53 (before mixes could
# carry ``order_seed``): a mix without the key consumes the generator as
# it did, so its schedules stay byte for byte what they were
CHAT_DIGESTS = (
    (3_718_000_001, None,
     "099f3463915b27e743b70db879c809e41b4a9e855c21b9903dd6684e5c278e39"),
    (3_718_000_002, 3.75,
     "77fa277b81e07b0c9e77345caf889d2b6c8ffa30b906c1905fbc22d8cfee1d5c"),
)


@pytest.mark.parametrize("seed,rate,digest", CHAT_DIGESTS,
                         ids=("mix_rate", "rate_3.75"))
def test_chat_schedule_is_unchanged(seed, rate, digest):
    s = traffic.make_schedule(_mix("chat"), seed, 51, 151936, rate=rate)
    assert _digest(s) == digest


def test_order_seed_must_be_an_int():
    mix = {**_mix("chat"), "order_seed": 1.5}
    with pytest.raises(ValueError, match="order_seed"):
        traffic.make_schedule(mix, 1, 10, 32000)


def test_describe_names_the_order_seed():
    assert "order seed" not in traffic.describe(_mix("chat"))
    assert traffic.describe({**_mix("chat"), "order_seed": 18}).endswith(
        ", order seed 18")


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
