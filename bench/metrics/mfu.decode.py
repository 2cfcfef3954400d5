"""Whole decode step's share of the chip's bf16 peak (model step,
``models/transformer.py``): the model FLOPs of the decode steps of the
traced slice (``bench/model_flops.py``, with each live row's KV length)
over the time of their ``decode`` spans times the peak."""
from bench.model_flops import decode_flops

UNIT = "%"


def read(run):
    spans = [dur for name, _, dur, _ in run.traced_spans if name == "decode"]
    if not spans or len(spans) != len(run.rec.decode_ctx):
        return None
    flops = sum(decode_flops(run.spec, ctx) for ctx in run.rec.decode_ctx)
    return 100 * flops / (sum(spans) * run.peaks["bf16_flops"])
