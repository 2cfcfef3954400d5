"""Whole prefill chunk's share of the chip's bf16 peak (model step,
``models/transformer.py``): the model FLOPs of the prefill chunks of the
traced slice (``bench/model_flops.py``, valid positions only) over the
time of their ``prefill_chunk`` spans times the peak."""
from bench.model_flops import prefill_flops

UNIT = "%"


def read(run):
    chunks = [(dur, a) for name, _, dur, a in run.traced_spans
              if name == "prefill_chunk"]
    if not chunks:
        return None
    flops = sum(prefill_flops(run.spec, a["start"], a["length"])
                for _, a in chunks)
    return 100 * flops / (sum(d for d, _ in chunks) * run.peaks["bf16_flops"])
