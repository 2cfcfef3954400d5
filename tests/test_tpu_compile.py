"""Compile the serve path's Pallas kernels for a described TPU v5e at
qwen2-1.5b widths (H=12, Hkv=2, dh=128, D=1536, V=151936).

Nothing runs: each test lowers and compiles with the TPU compiler that
ships with JAX for a chip that is described, not attached, and asserts
that the program holds a Mosaic kernel (``tpu_custom_call``).  This is
what interpret mode cannot show — block shapes that break the (8, 128)
tiling, kernels that overflow scoped VMEM, a step that does not fit the
chip's memory.  The topology is described inside a fixture (never at
import), so every test worker collects the same tests and only the one
running this file loads the TPU library; where it cannot be described,
the tests skip.  The persistent compile cache is off around the
compiles: an entry written for a described chip cannot be read back.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import MuxSpec

CFG = get_config("qwen2-1.5b", reduced=False)
H, HKV, DH = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
D, V = CFG.d_model, CFG.vocab_size
F = 2 * D                      # RSA demux hidden width (MuxSpec default)
N_MUX, ROWS = 2, 2
BS, MB, POOL = 16, 8, 64       # block size, blocks per row, pool blocks
CHUNK = 32
T_MAX = 16 * CHUNK             # rows x chunk of a 16-row chunk batch
PAGE_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
               "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2, with the persistent compile
    cache off and the TPU compiler's logs kept out of the file system."""
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            from jax.experimental import topologies
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _pool_args(chip, pages, rows=ROWS, mb=MB, bs=BS, pool=POOL, hkv=HKV,
               dh=DH):
    dt = PAGE_DTYPES[pages]
    args = [_shape(chip, (pool, bs, hkv, dh), dt),
            _shape(chip, (pool, bs, hkv, dh), dt),
            _shape(chip, (rows, mb), jnp.int32),
            _shape(chip, (pool, bs), jnp.int32)]
    scales = ([_shape(chip, (pool, bs, hkv), jnp.float32)] * 2
              if pages in ("int8", "fp8") else [])
    return args, scales


# (rows, blocks per row, block size, pool blocks, heads, kv heads, head
# size) and query dtype: the small serve shape above; the benchmark's
# chat cell (32 rows of 20 x 128-token blocks over 641 pages, bf16),
# where the page buffers are largest; and h2o-danube-1.8b's heads, whose
# head size of 80 is not a whole 128-lane tile
CHAT_POOL = (32, 20, 128, 641, H, HKV, DH)
DANUBE_POOL = (8, 21, 256, 169, 32, 8, 80)


@pytest.mark.parametrize("pages,shape,q_dtype", [
    *[(p, (ROWS, MB, BS, POOL, H, HKV, DH), jnp.float32)
      for p in PAGE_DTYPES],
    ("bf16", CHAT_POOL, jnp.bfloat16), ("int8", CHAT_POOL, jnp.bfloat16),
    ("bf16", DANUBE_POOL, jnp.bfloat16)])
def test_paged_attention_compiles(chip, pages, shape, q_dtype):
    from repro.kernels.paged_attention import paged_attention
    rows, mb, bs, pool_blocks, h, hkv, dh = shape
    pool, scales = _pool_args(chip, pages, rows, mb, bs, pool_blocks, hkv,
                              dh)

    def f(q, kp, vp, bt, pp, qp, *sc):
        kw = dict(k_scales=sc[0], v_scales=sc[1]) if sc else {}
        return paged_attention(q, kp, vp, bt, pp, qp, **kw)

    c = _compile(f, _shape(chip, (rows, 1, h, dh), q_dtype), *pool,
                 _shape(chip, (rows,), jnp.int32), *scales)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("pages", list(PAGE_DTYPES))
def test_paged_prefill_attention_compiles(chip, pages):
    from repro.kernels.paged_attention import paged_prefill_attention
    pool, scales = _pool_args(chip, pages)

    def f(q, kp, vp, bt, pp, qs, ql, *sc):
        kw = dict(k_scales=sc[0], v_scales=sc[1]) if sc else {}
        return paged_prefill_attention(q, kp, vp, bt, pp, qs, ql, **kw)

    c = _compile(f, _shape(chip, (ROWS, CHUNK, H, DH), jnp.float32), *pool,
                 _shape(chip, (ROWS,), jnp.int32),
                 _shape(chip, (ROWS,), jnp.int32), *scales)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mux_embed_combine_compiles(chip, dtype):
    from repro.kernels.mux_embed import mux_embed_combine
    c = _compile(functools.partial(mux_embed_combine, scale=D ** 0.5,
                                   out_dtype=dtype),
                 _shape(chip, (N_MUX, T_MAX), jnp.int32),
                 _shape(chip, (V, D), jnp.float32),
                 _shape(chip, (N_MUX, D), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("t", [ROWS, T_MAX])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_demux_rsa_fused_exit_compiles(chip, dtype, t):
    """The decode exit: final RMS norm + RSA demux MLP + demux LayerNorm
    in one launch, at the decode grid and the largest chunk batch — the
    default tiles must fit scoped VMEM in fp32 and bf16."""
    from repro.kernels.demux_rsa import demux_rsa

    def f(h, k, w1h, w1k, b1, w2, b2, es, xs, xb):
        return demux_rsa(h, k, w1h, w1k, b1, w2, b2, entry_kind="rms",
                         entry_scale=es, exit_scale=xs, exit_bias=xb)

    c = _compile(f, _shape(chip, (t, D), dtype),
                 _shape(chip, (N_MUX, D), dtype),
                 _shape(chip, (D, F), dtype), _shape(chip, (D, F), dtype),
                 _shape(chip, (F,), dtype), _shape(chip, (F, D), dtype),
                 _shape(chip, (D,), dtype),
                 *[_shape(chip, (D,), jnp.float32)] * 3)
    assert "tpu_custom_call" in c.as_text()


def test_serve_decode_step_fits_one_chip(chip, monkeypatch):
    """The runtime's jitted decode step, kernel arm, at the full 28-layer
    qwen2-1.5b in fp32: it holds the paged attention, mux_embed and
    demux_rsa kernels, and its arguments plus temporaries fit one v5e's
    HBM.  The kernel wrappers ask the backend (the CPU here) whether to
    interpret; the test steers them to Mosaic."""
    from repro.kernels import ops
    from repro.models import TransformerLM
    from repro.serve import ServeConfig
    from repro.serve.runtime import ServeRuntime
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mux = MuxSpec(n=N_MUX)
    sc = ServeConfig(cfg=CFG, kind="lm", mux=mux, capacity=64,
                     dtype=jnp.float32, cache_layout="paged", block_size=BS)
    params = jax.tree.map(
        lambda s: _shape(chip, s.shape, s.dtype),
        jax.eval_shape(lambda k: TransformerLM.init(k, CFG, mux),
                       jax.random.PRNGKey(0)))
    rt = ServeRuntime(params, sc, ROWS, chunk=CHUNK, use_kernels=True)
    cache = jax.tree.map(lambda a: _shape(chip, a.shape, a.dtype), rt.cache)
    nb = rt.nb
    c = rt._decode_jit.lower(
        params, cache, _shape(chip, (nb, 1), jnp.int32),
        _shape(chip, (ROWS,), jnp.int32),
        _shape(chip, (nb,), jnp.float32), _shape(chip, (nb,), jnp.int32),
        _shape(chip, (nb,), jnp.float32), _shape(chip, (nb,), jnp.int32),
        _shape(chip, (nb,), jnp.int32)).compile()
    assert c.as_text().count("tpu_custom_call") == 3
    assert rt.trace_counts == {"decode": 1}
    mem = c.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert need < V5E_HBM_BYTES, need
