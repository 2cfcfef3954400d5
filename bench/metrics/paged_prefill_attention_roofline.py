"""Share of its roofline that the chunked-prefill attention kernel
reaches (kernels, ``kernels/paged_attention.py``): the least time of its
calls in the traced slice, each the larger of its operations over the
bf16 peak and its bytes over the HBM bandwidth
(``bench/kernels/paged_prefill_attention.py``, one call per layer of
each chunk), over the kernel's device time in the trace."""
from bench.trace_reduce import kernel_seconds

UNIT = "%"
KERNEL = "paged_prefill_attention"


def read(run):
    chunks = [a for name, _, _, a in run.traced_spans
              if name == "prefill_chunk"]
    if run.trace is None or not chunks:
        return None
    secs = kernel_seconds(run.trace, run.kernel_match(KERNEL))
    if not secs:
        return None
    k, s, p = run.kernel(KERNEL), run.spec, run.peaks
    least = 0.0
    for a in chunks:
        f, b = k.cost(a["start"], a["length"], heads=s["heads"],
                      kv_heads=s["kv_heads"], head_dim=s["head_dim"],
                      block=run.block, window=s["window"])
        least += max(f / p["bf16_flops"], b / p["hbm_bytes_per_s"])
    return 100 * least * s["layers"] / secs
