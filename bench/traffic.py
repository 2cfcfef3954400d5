"""One general generator for every traffic mix (``bench/traffic/<mix>.json``).

A mix file gives the arrival process, the rate in requests per second,
the warm-up length, and the prompt and output length distributions.
``make_schedule`` turns it into the requests of one run: the warm-up
segment, then the measured window.

So that seeds change the order of the work and not the work itself,
each segment's sizes are fixed by the mix: a segment of D seconds at
rate r holds round(r * D) requests whose inter-arrival gaps are the
stratified quantiles of the exponential distribution (rescaled to sum
to D) and whose lengths are the stratified quantiles of their lognormal
distributions, clipped to their bounds.  The seed orders gaps, prompt
lengths and output lengths independently, in a balanced order: the
sorted values fall into ``BANDS`` equal bands, and each run of
``BANDS`` consecutive requests takes one value of every band, so that
every few seconds of a segment carry the same mix whatever the seed
(a plain shuffle let one seed put its longest outputs at the window's
end, where they count only in part).  Every token id is drawn uniformly
from [1, vocab) (id 0 is the serve path's pad).
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

ARRIVALS = ("poisson",)
BANDS = 8


def _quantiles(m: int):
    return (np.arange(m) + 0.5) / m


def length_quantiles(dist: dict, m: int) -> np.ndarray:
    """m stratified lengths of a clipped lognormal, ascending."""
    if dist.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {dist.get('dist')!r}")
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(m)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z)).astype(np.int64)
    return np.clip(x, dist["min"], dist["max"])


def gap_quantiles(rate: float, duration: float, m: int) -> np.ndarray:
    """m stratified exponential inter-arrival gaps summing to duration."""
    g = -np.log1p(-_quantiles(m)) / rate
    return g * (duration / g.sum())


@dataclass
class Schedule:
    due: np.ndarray            # (R,) seconds after the traffic starts
    prompts: list              # R int32 arrays
    max_new: np.ndarray        # (R,) output tokens per request
    window_start: float        # seconds after the traffic starts

    def __len__(self):
        return len(self.due)


def balanced_order(rng, values, bands: int = BANDS) -> np.ndarray:
    """``values`` (ascending) in a seeded order in which each run of
    ``bands`` consecutive items takes one item of each of ``bands``
    equal bands of the values (the last run takes what is left)."""
    m = len(values)
    per = -(-m // bands)                       # items per band = runs
    cols = [rng.permutation(values[j * per:(j + 1) * per])
            for j in range(bands)]
    out = []
    for t in range(per):
        run = [c[t] for c in cols if t < len(c)]
        out.extend(rng.permutation(run))
    return np.asarray(out, dtype=np.asarray(values).dtype)


def _segment(mix, rng, start, duration, vocab):
    m = int(round(mix["rate_rps"] * duration))
    if m == 0:
        return np.zeros(0), [], np.zeros(0, np.int64)
    gaps = balanced_order(rng, gap_quantiles(mix["rate_rps"], duration, m))
    due = start + np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    plen = balanced_order(rng, length_quantiles(mix["prompt_len"], m))
    olen = balanced_order(rng, length_quantiles(mix["output_len"], m))
    prompts = [rng.integers(1, vocab, size=int(n), dtype=np.int32)
               for n in plen]
    return due, prompts, olen


def make_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                  rate: float | None = None) -> Schedule:
    """The requests of one run: ``mix['warmup_s']`` seconds of warm-up,
    then ``seconds`` of window.  ``rate`` overrides the mix's rate (the
    knee sweep)."""
    if mix.get("arrivals", "poisson") not in ARRIVALS:
        raise ValueError(f"unknown arrival process {mix.get('arrivals')!r}")
    if rate is not None:
        mix = {**mix, "rate_rps": rate}
    rng = np.random.default_rng(seed)
    warm = float(mix["warmup_s"])
    parts = [_segment(mix, rng, 0.0, warm, vocab),
             _segment(mix, rng, warm, float(seconds), vocab)]
    return Schedule(due=np.concatenate([p[0] for p in parts]),
                    prompts=[x for p in parts for x in p[1]],
                    max_new=np.concatenate([p[2] for p in parts]),
                    window_start=warm)


def mean_length(dist: dict, m: int = 4096) -> float:
    return float(length_quantiles(dist, m).mean())


def describe(mix: dict) -> str:
    return (f"{mix.get('arrivals', 'poisson')} {mix['rate_rps']} req/s, "
            f"prompts ~{mean_length(mix['prompt_len']):.0f} tokens, "
            f"outputs ~{mean_length(mix['output_len']):.0f} tokens "
            f"(means), warm-up {mix['warmup_s']} s")

