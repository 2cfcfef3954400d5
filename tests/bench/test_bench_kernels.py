"""Operations and bytes of the kernels (``bench/kernels/*.py``) and the
model FLOPs of the step programs (``bench/model_flops.py``), against
shapes worked by hand."""
import importlib.util
import pathlib

import pytest

from bench import model_flops

ROOT = pathlib.Path(__file__).resolve().parents[2]
SHAPE = dict(heads=4, kv_heads=2, head_dim=8, block=16)
PAGE = 16 * (2 * 2 * 8 * 2 + 4)          # K and V of 2 heads in bf16 + positions


def _kernel(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "kernels" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("window, flops, pages", [
    (None, 4 * 4 * 8 * 33, 3),           # keys 0..32: pages 0, 1, 2
    (20, 4 * 4 * 8 * 20, 3),             # keys 13..32: still pages 0..2
    (10, 4 * 4 * 8 * 10, 2),             # keys 23..32: pages 1, 2
])
def test_paged_attention_cost(window, flops, pages):
    k = _kernel("paged_attention")
    f, b = k.cost([33], window=window, **SHAPE)
    assert f == flops
    assert b == pages * PAGE + 2 * 4 * 8 * 2
    f2, b2 = k.cost([33, 33], window=window, **SHAPE)
    assert (f2, b2) == (2 * f, 2 * b)


@pytest.mark.parametrize("window, keys", [
    (None, 8 * 16 + 36),                 # queries 16..23 see 17..24 keys
    (10, 8 * 10),                        # keys from 7: pages 0, 1
    (4, 8 * 4),                          # keys from 13: pages 0, 1
])
def test_paged_prefill_attention_cost(window, keys):
    k = _kernel("paged_prefill_attention")
    assert k.keys_attended(16, 8, window) == keys
    f, b = k.cost(16, 8, window=window, **SHAPE)
    assert f == 4 * 4 * 8 * keys
    first = 0 if window is None else max(0, 17 - window)
    assert b == (23 // 16 - first // 16 + 1) * PAGE + 2 * 8 * 4 * 8 * 2


def test_kernels_name_their_trace_ops():
    """Operation names as a v5e profiler trace gives them: the HLO
    instruction, named after the Pallas kernel."""
    pa, pp = _kernel("paged_attention"), _kernel("paged_prefill_attention")
    call = ('custom-call(s32[32,20]{1,0} %bitcast.263), '
            'custom_call_target="tpu_custom_call"')
    dec = ("%paged_attention.8 = bf16[32,2,6,128]{3,2,1,0} " + call, "")
    pre = ("%paged_prefill_attention.8 = bf16[1,2,1536,128]{3,2,1,0} "
           + call, "")
    other = ("%reshape.327 = bf16[32,1536]{1,0} reshape(bf16[32,2,6,128]"
             "{3,2,1,0} %paged_attention.8)", "")
    assert pa.in_trace(*dec) and not pa.in_trace(*pre)
    assert pp.in_trace(*pre) and not pp.in_trace(*dec)
    assert not pa.in_trace(*other) and not pp.in_trace(*other)


def test_model_flops_by_hand():
    s = dict(d=4, layers=1, heads=2, kv_heads=1, head_dim=2, ffn=8,
             vocab=10, n_mux=2, demux_hidden=8, window=None)
    backbone = 2 * (4 * (2 + 2) * 2 + 2 * 2 * 4 + 3 * 4 * 8)      # 288
    token_out = 2 * 4 * 8 * (1 + 2) + 2 * 2 * 4 * 10              # 352
    mux = 2 * 2 * 4
    attn = 4 * 2 * 2                                               # per key
    assert model_flops.decode_flops(s, [3, 5]) == (
        2 * (backbone + mux + token_out) + attn * 8)
    assert model_flops.prefill_flops(s, 2, 3) == (
        3 * (backbone + mux) + attn * (3 + 4 + 5) + token_out)
    s["window"] = 4
    assert model_flops.decode_flops(s, [3, 5]) == (
        2 * (backbone + mux + token_out) + attn * (3 + 4))
