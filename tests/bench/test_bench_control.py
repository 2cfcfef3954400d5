"""The control of the output check: the float32 reference with every
matmul operand rounded to float8 e4m3 (the precision below the served
bfloat16) put in the program's place has to come out not correct.

At this tiny size (hidden 64, 2 layers, vocab 512) the logits spread by
about 0.16, bf16 rounding moves a served token's logit by at most about
0.002 below the reference's best, and float8 by about 0.01 and more.
The tiny limit, 0.004, sits between; the cells' own limits are set from
chip readings at their sizes (PERF.md).
"""
import copy

from bench import check
from conftest import serve_requests

TINY_LIMIT = 0.004
LENGTHS = [(37, 16), (21, 16), (30, 16), (29, 16)]


def test_fp8_control_fails_where_bf16_passes(tiny_cell):
    params, spec, conf, prompts, groups, served = serve_requests(
        tiny_cell, "bfloat16", 2, LENGTHS)
    conf = copy.deepcopy(conf)
    conf["check"].update(logit_gap_limit=TINY_LIMIT, min_tokens=64)

    class Sched:
        pass
    sched = Sched()
    sched.prompts = prompts
    out, readings = check.check_groups(params, spec, conf, sched, groups,
                                       served, seed=2, quant="fp8")
    assert out["tokens_compared"]["value"] == 64
    assert readings["program_gap_max"] <= TINY_LIMIT
    # the control, in the program's place, fails the comparison itself
    assert out["logit_gap_max"]["value"] == readings["control_gap_max"]
    assert out["logit_gap_max"]["value"] > TINY_LIMIT
