"""Decide ``correct``: what the window served against the float32 reference.

After the window has closed and the program's state is freed, a sample
of the row groups that finished, drawn from the seed and always holding
the longest, is run through ``bench/reference/decoder.py``: each group's
N streams exactly as the serve path fed them (prompts right-padded to
the group's longest with the pad id, then each stream's served tokens,
then pads once a stream is done).  At every position where a token was
served, the reference's best logit minus its logit for the served token
is the gap; the number compared is the widest gap over the sample.  A
greedy server that computes what the reference computes serves the
reference's best token up to rounding, so its gap stays near 0; a wrong
page, mask, mux key or head shows as a gap of the order of the logits'
spread.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench.reference import decoder as ref

PAD_ID = 0                 # the serve path's pad token (ServeRuntime pad_id)


def group_arrays(group, prompts, served, n_mux: int, length: int):
    """(tokens (N, length), stream, position, served token) of one group."""
    lpad = group["l_pad"]
    tokens = np.full((n_mux, length), PAD_ID, np.int32)
    cols = ([], [], [])
    for i, k in group["slots"].items():
        p, out = prompts[k], served[k]
        tokens[i, :len(p)] = p
        tokens[i, lpad:lpad + len(out) - 1] = out[:-1]
        for t, tok in enumerate(out):
            cols[0].append(i)
            cols[1].append(lpad - 1 + t)
            cols[2].append(tok)
    return tokens, *(np.asarray(c, np.int32) for c in cols)


def sample_groups(groups, served, seed: int, tokens: int, max_groups: int):
    """The longest finished group, then others in a seeded order until
    ``tokens`` served tokens or ``max_groups`` groups are in."""
    if not groups:
        return []
    span = [g["l_pad"] + max(len(served[k]) for k in g["slots"].values())
            for g in groups]
    first = int(np.argmax(span))
    order = [first] + [int(i) for i in np.random.default_rng(
        [seed, 12]).permutation(len(groups)) if i != first]
    chosen, n = [], 0
    for i in order:
        if n >= tokens or len(chosen) >= max_groups:
            break
        chosen.append(groups[i])
        n += sum(len(served[k]) for k in groups[i]["slots"].values())
    return chosen


def reference_length(capacity: int) -> int:
    """One padded row length for every group, so the reference compiles
    once: the capacity, rounded up to the attention query block."""
    qb = min(ref.Q_BLOCK, capacity)
    return -(-capacity // qb) * qb


def gaps(params, spec, tokens, stream, pos, target, quant=None):
    """Per served position: reference best logit minus the reference
    logit of ``target``; with ``quant`` also the gap of the token that the
    quantized reference puts first (the control)."""
    h = ref.demuxed_hidden(params, spec, tokens)
    best, at, top = ref.position_stats(params, spec, h, stream, pos, target)
    out = {"gap": best - at, "agree": int((top == target).sum())}
    if quant is not None:
        hq = ref.demuxed_hidden(params, spec, tokens, quant=quant)
        _, _, top_q = ref.position_stats(params, spec, hq, stream, pos,
                                         target, quant=quant)
        best2, at_q, _ = ref.position_stats(params, spec, h, stream, pos,
                                            top_q)
        out["control_gap"] = best2 - at_q
    return out


def check_groups(params, spec: dict, conf: dict, schedule, finished, served,
                 seed: int, quant: str | None = None):
    """The checks of one run, {name: {value, limit, holds}}, and the
    readings behind them.  With ``quant`` the control takes the program's
    place: the tokens that the reference rounded to ``quant`` puts first
    are compared instead of the served ones, so the check has to make
    ``correct`` false; the program's own widest gap stays a reading."""
    c = conf["check"]
    chosen = sample_groups(finished, served, seed, c["tokens"],
                           c["max_groups"])
    length = reference_length(conf["serve"]["capacity"])
    t = time.perf_counter()
    widest, n_tok, agree, control = [], 0, 0, []
    for g in chosen:
        arrays = group_arrays(g, schedule.prompts, served, spec["n_mux"],
                              length)
        r = gaps(params, spec, *arrays, quant=quant)
        widest.append(float(r["gap"].max()))
        n_tok += len(r["gap"])
        agree += r["agree"]
        if quant is not None:
            control.append(float(r["control_gap"].max()))
    readings = {"program_gap_max": max(widest) if widest else None,
                "control_gap_max": max(control) if control else None}
    print(f"check: {len(chosen)} of {len(finished)} finished row groups, "
          f"{n_tok} served tokens against the float32 reference in "
          f"{time.perf_counter() - t:.1f} s; reference argmax agrees on "
          f"{agree}/{n_tok}; widest gap per group {widest}"
          + (f"; {quant} control per group {control}" if quant else ""),
          flush=True)
    compared = "control_gap_max" if quant is not None else "program_gap_max"
    out = {"logit_gap_max": {"value": readings[compared],
                             "limit": c["logit_gap_limit"], "holds": "<="},
           "tokens_compared": {"value": n_tok, "limit": c["min_tokens"],
                               "holds": ">="}}
    return out, readings


def print_checks(checks: dict):
    """Each number compared beside its limit, as the last stderr lines."""
    for name, c in checks.items():
        if isinstance(c, dict):
            print(f"check {name}: {c['value']} {c['holds']} {c['limit']}",
                  file=sys.stderr, flush=True)
