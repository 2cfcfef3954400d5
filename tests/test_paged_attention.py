"""Paged decode-attention: Pallas kernel (interpret mode) vs the pure-JAX
reference, and both vs the contiguous ``decode_attention`` kernel on an
equivalent cache.

The differential kernel-parity layer at the bottom sweeps page-storage
dtypes {fp32, bf16, int8, fp8} × {decode, chunked-prefill, sharded} ×
edge shapes.  Tolerances are derived analytically from the stored
scales / storage precision (``core.quant.paged_attention_error_bound``
and the bf16 relative-rounding analogue), never hand-tuned: each
quantized kernel run is asserted (a) against the dequantize-then-attend
oracle at the kernels' own arithmetic tolerance — the fused dequant is
exactly ``payload * scale`` — and (b) against the pristine fp32 oracle
within the analytic bound."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.kernels import ops, ref
from repro.launch.mesh import make_serve_mesh

KEY = jax.random.PRNGKey(0)
# the TPU interpreter, for the decode kernel's page DMA: a copy lands only
# when it is waited on and unwritten VMEM reads as NaN, so a page read
# before its copy completes, or never fetched, shows in the output (the
# plain interpreter, which the serve path uses on the CPU, copies at once).
# Its callbacks dispatch JAX operations, so read each result back before
# dispatching anything else: a second dispatch meanwhile can deadlock.
TPU_INTERPRET = pltpu.InterpretParams()


def build_pool(lens, *, num_blocks, block_size, max_blocks, hkv, dh, key):
    """Allocate per-row blocks (block 0 = trash) and fill them with random
    K/V; returns (k_pages, v_pages, block_tables, page_pos)."""
    ks = jax.random.split(key, 2)
    kp = jax.random.normal(ks[0], (num_blocks, block_size, hkv, dh))
    vp = jax.random.normal(ks[1], (num_blocks, block_size, hkv, dh))
    bt = np.full((len(lens), max_blocks), -1, np.int32)
    ppos = np.full((num_blocks, block_size), -1, np.int32)
    free = list(range(1, num_blocks))
    for b, n in enumerate(lens):
        if n < 0:
            continue
        nb = -(-n // block_size) if n else 0
        blocks = [free.pop() for _ in range(nb)]
        bt[b, :nb] = blocks
        for t in range(n):
            ppos[blocks[t // block_size], t % block_size] = t
    return kp, vp, jnp.asarray(bt), jnp.asarray(ppos)


@pytest.mark.parametrize("hkv,window", [(2, None), (2, 12), (8, None),
                                        (2, 20)])   # window from mid-page
def test_paged_kernel_matches_ref(hkv, window):
    B, H, DH, BS, MB, P = 3, 8, 16, 8, 6, 16
    q = jax.random.normal(KEY, (B, 1, H, DH))
    # heterogeneous rows, one inactive (-1)
    lens = [37, 12, -1]
    kp, vp, bt, ppos = build_pool(lens, num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=hkv, dh=DH,
                                  key=jax.random.fold_in(KEY, hkv))
    q_pos = jnp.asarray([36, 11, -1], jnp.int32)
    got = ops.paged_attention(q, kp, vp, bt, ppos, q_pos,
                              window=window, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, ppos, q_pos,
                                   window=window)
    # inactive rows are fully masked; their output is caller-discarded
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2],
                               atol=3e-5, rtol=1e-4)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("window", [None, 16])
def test_paged_matches_contiguous_decode_attention(window):
    """Rows laid out contiguously in the pool must reproduce the ring
    kernel's output on the equivalent contiguous cache."""
    B, H, HKV, DH, BS, MB = 2, 8, 2, 16, 8, 6
    P = B * MB + 1
    q = jax.random.normal(KEY, (B, 1, H, DH))
    n, q_pos = 40, 39
    kp, vp, bt, ppos = build_pool([n] * B, num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    got = ops.paged_attention(q, kp, vp, bt, ppos,
                              jnp.full((B,), q_pos, jnp.int32),
                              window=window, interpret=True)
    # materialize each row's contiguous equivalent
    kc = np.zeros((B, MB * BS, HKV, DH), np.float32)
    vc = np.zeros_like(kc)
    pos_c = np.full((MB * BS,), -1, np.int32)
    btn, kpn, vpn = map(np.asarray, (bt, kp, vp))
    for b in range(B):
        for t in range(n):
            pg = btn[b, t // BS]
            kc[b, t] = kpn[pg, t % BS]
            vc[b, t] = vpn[pg, t % BS]
    pos_c[:n] = np.arange(n)
    want = ops.decode_attention(q, jnp.asarray(kc), jnp.asarray(vc),
                                jnp.asarray(pos_c), q_pos=q_pos,
                                window=window, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


# ------------------------------------------------------- edge shapes

def test_paged_decode_single_block_rows():
    """Rows whose whole context fits in ONE block (table width 1), plus
    a row at position 0 (empty context except its own token)."""
    B, H, HKV, DH, BS, MB, P = 3, 4, 2, 8, 8, 1, 8
    q = jax.random.normal(KEY, (B, 1, H, DH))
    kp, vp, bt, ppos = build_pool([8, 3, 1], num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    q_pos = jnp.asarray([7, 2, 0], jnp.int32)
    got = ops.paged_attention(q, kp, vp, bt, ppos, q_pos, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, ppos, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


def test_paged_prefill_single_block_rows():
    """Chunked-prefill kernel with a width-1 block table: the whole
    prompt (and the chunk) lives in a single block."""
    B, H, HKV, DH, BS, MB, P, LQ = 2, 4, 2, 8, 8, 1, 8, 4
    q = jax.random.normal(KEY, (B, LQ, H, DH))
    kp, vp, bt, ppos = build_pool([8, 6], num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    q_start = jnp.asarray([4, 2], jnp.int32)
    q_len = jnp.asarray([4, 4], jnp.int32)
    got = ops.paged_prefill_attention(q, kp, vp, bt, ppos, q_start, q_len,
                                      interpret=True)
    want = ref.paged_prefill_attention_ref(q, kp, vp, bt, ppos, q_start,
                                           q_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


def test_paged_prefill_chunk_on_block_boundary():
    """A chunk that starts AND ends exactly on block boundaries (start a
    multiple of the block size, length == block size) — the boundary
    arithmetic must not lose the edge slots."""
    B, H, HKV, DH, BS, MB, P = 2, 4, 2, 8, 4, 6, 16
    LQ = BS
    q = jax.random.normal(KEY, (B, LQ, H, DH))
    kp, vp, bt, ppos = build_pool([16, 12], num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    q_start = jnp.asarray([12, 8], jnp.int32)    # both on block edges
    q_len = jnp.asarray([4, 4], jnp.int32)       # chunk end == block end
    got = ops.paged_prefill_attention(q, kp, vp, bt, ppos, q_start, q_len,
                                      interpret=True)
    want = ref.paged_prefill_attention_ref(q, kp, vp, bt, ppos, q_start,
                                           q_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("lens,q_pos", [
    ([29, 13, 7], [28, 12, 6]),          # non-power-of-two lengths
    ([31, 17, 11], [30, 16, 10]),
])
def test_paged_decode_non_pow2_lengths(lens, q_pos):
    B, H, HKV, DH, BS, MB, P = 3, 8, 2, 16, 8, 4, 16
    q = jax.random.normal(KEY, (B, 1, H, DH))
    kp, vp, bt, ppos = build_pool(lens, num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH,
                                  key=jax.random.fold_in(KEY, lens[0]))
    got = ops.paged_attention(q, kp, vp, bt, ppos,
                              jnp.asarray(q_pos, jnp.int32),
                              interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, ppos,
                                   jnp.asarray(q_pos, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


def test_paged_prefill_non_pow2_chunk():
    """Lq = 7 (not a power of two) with partially padded rows."""
    B, H, HKV, DH, BS, MB, P, LQ = 2, 4, 2, 8, 8, 4, 12, 7
    q = jax.random.normal(KEY, (B, LQ, H, DH))
    kp, vp, bt, ppos = build_pool([23, 11], num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    q_start = jnp.asarray([16, 6], jnp.int32)
    q_len = jnp.asarray([7, 5], jnp.int32)       # row 1: 2 padded queries
    got = ops.paged_prefill_attention(q, kp, vp, bt, ppos, q_start, q_len,
                                      interpret=True)
    want = ref.paged_prefill_attention_ref(q, kp, vp, bt, ppos, q_start,
                                           q_len)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0],
                               atol=3e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got)[1, :5],
                               np.asarray(want)[1, :5],
                               atol=3e-5, rtol=1e-4)


def test_paged_kernels_single_row_batch():
    """B = 1 (the N_mux == 1, one-row edge): both kernels against the
    oracle."""
    H, HKV, DH, BS, MB, P = 4, 2, 8, 4, 4, 8
    kp, vp, bt, ppos = build_pool([13], num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    q = jax.random.normal(KEY, (1, 1, H, DH))
    got = ops.paged_attention(q, kp, vp, bt, ppos,
                              jnp.asarray([12], jnp.int32), interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, ppos,
                                   jnp.asarray([12], jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)
    qc = jax.random.normal(jax.random.fold_in(KEY, 9), (1, 4, H, DH))
    got = ops.paged_prefill_attention(qc, kp, vp, bt, ppos,
                                      jnp.asarray([9], jnp.int32),
                                      jnp.asarray([4], jnp.int32),
                                      interpret=True)
    want = ref.paged_prefill_attention_ref(qc, kp, vp, bt, ppos,
                                           jnp.asarray([9], jnp.int32),
                                           jnp.asarray([4], jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


def test_unallocated_table_entries_stay_masked():
    """-1 table entries are clamped to page 0 by the reference's gather
    (the kernel never fetches them); even a 'poisoned' page 0 (seemingly
    valid positions) must not leak into the output of either."""
    B, H, HKV, DH, BS, MB, P = 1, 4, 2, 8, 4, 4, 12
    q = jax.random.normal(KEY, (B, 1, H, DH))
    kp, vp, bt, ppos = build_pool([10], num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    assert (np.asarray(bt)[0] == -1).sum() > 0     # row has unused entries
    q_pos = jnp.asarray([9], jnp.int32)
    clean_ref = ref.paged_attention_ref(q, kp, vp, bt, ppos, q_pos)
    poisoned = jnp.asarray(np.asarray(ppos)).at[0].set(jnp.arange(BS))
    for fn in (ref.paged_attention_ref,
               lambda *a, **k: ops.paged_attention(*a, interpret=True, **k)):
        got = fn(q, kp, vp, bt, poisoned, q_pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(clean_ref),
                                   atol=3e-5, rtol=1e-4)


# ==================================================================
# differential parity layer: {fp32, bf16, int8, fp8} page storage
# ==================================================================

# the kernels' own arithmetic tolerance (identical inputs, reordered
# f32 accumulation) — the same constant the unquantized tests use above
KERNEL_ATOL = 3e-5
BF16_REL = 2.0 ** -8            # bf16 half-ulp relative rounding error

QUANT_KINDS = ["int8"] + (["fp8"] if quant.has_fp8() else [])
STORE_KINDS = ["fp32", "bf16"] + QUANT_KINDS


def _stored_pool(kp, vp, kind):
    """Store the fp32 pool at ``kind`` precision as KVPool would.
    Returns (k_store, v_store, scale_kwargs, k_dequant, v_dequant) —
    the dequant pair is what the fused kernel's page loads decode to."""
    if kind in ("fp32", "bf16"):
        dt = quant.kv_store_dtype(kind)
        kq, vq = kp.astype(dt), vp.astype(dt)
        return (kq, vq, {},
                kq.astype(jnp.float32), vq.astype(jnp.float32))
    kq, ks = quant.quantize_kv(kp, kind)
    vq, vs = quant.quantize_kv(vp, kind)
    return (kq, vq, {"k_scales": ks, "v_scales": vs},
            quant.dequantize_kv(kq, ks), quant.dequantize_kv(vq, vs))


def _storage_bound(q, kind, kp, vp, scale_kw):
    """Analytic |kernel - pristine fp32 oracle| bound for ``kind``
    storage (0 for fp32 pages; the softmax-Lipschitz bound of
    ``core.quant`` for int8/fp8; its relative-rounding analogue —
    e = BF16_REL * |x| — for bf16)."""
    if kind == "fp32":
        return 0.0
    if kind == "bf16":
        qf = jnp.asarray(q, jnp.float32)
        q_l1 = float(jnp.max(jnp.sum(jnp.abs(qf), axis=-1)))
        k_max = float(jnp.max(jnp.abs(kp)))
        v_max = float(jnp.max(jnp.abs(vp)))
        e_k, e_v = BF16_REL * k_max, BF16_REL * v_max
        return (2.0 * q_l1 * e_k * qf.shape[-1] ** -0.5 * (v_max + e_v)
                + e_v)
    return float(quant.paged_attention_error_bound(
        q, scale_kw["k_scales"], scale_kw["v_scales"], kind))


@pytest.mark.parametrize("kind", STORE_KINDS)
@pytest.mark.parametrize("lens,q_pos,mb,holes", [
    ([37, 12, -1], [36, 11, -1], 6, []),  # heterogeneous + inactive row
    ([8, 3, 1], [7, 2, 0], 1, []),        # whole rows inside ONE block
    ([29, 13, 7], [28, 12, 6], 4, []),    # non-power-of-two lengths
    # query on the last slot of a page, the first of the next, and the
    # last again; a row with no live page (q_pos -1) beside them
    ([8, 9, 16, -1], [7, 8, 15, -1], 3, []),
    ([32, 32, 5], [31, 31, 4], 4, []),    # full-table rows: every block
    ([37, 30], [36, 29], 6, [(0, 2), (1, 1)]),  # -1 holes in the range
])
def test_paged_decode_storage_parity(kind, lens, q_pos, mb, holes):
    B, H, HKV, DH, BS, P = len(lens), 8, 2, 16, 8, 32
    q = jax.random.normal(KEY, (B, 1, H, DH))
    kp, vp, bt, ppos = build_pool(lens, num_blocks=P, block_size=BS,
                                  max_blocks=mb, hkv=HKV, dh=DH,
                                  key=jax.random.fold_in(KEY, mb))
    for r, j in holes:                    # unallocated mid-range entries
        bt = bt.at[r, j].set(-1)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    ks, vs, scale_kw, k_hi, v_hi = _stored_pool(kp, vp, kind)
    got = np.asarray(ops.paged_attention(q, ks, vs, bt, ppos, q_pos,
                                         interpret=TPU_INTERPRET,
                                         **scale_kw))
    act = np.asarray(q_pos) >= 0                  # active rows only
    # (a) fused dequant == dequantize-then-attend oracle
    want = (ref.paged_attention_quant_ref(
                q, ks, vs, scale_kw["k_scales"], scale_kw["v_scales"],
                bt, ppos, q_pos) if scale_kw
            else ref.paged_attention_ref(q, k_hi, v_hi, bt, ppos, q_pos))
    np.testing.assert_allclose(np.asarray(got)[act], np.asarray(want)[act],
                               atol=KERNEL_ATOL, rtol=1e-4)
    # (b) within the analytic bound of the pristine fp32 oracle
    pristine = ref.paged_attention_ref(q, kp, vp, bt, ppos, q_pos)
    bound = _storage_bound(q, kind, kp, vp, scale_kw) + KERNEL_ATOL
    err = np.abs(np.asarray(got)[act] - np.asarray(pristine)[act])
    assert err.max() <= bound, (kind, float(err.max()), bound)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("window", [None, 12])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_paged_decode_dead_pages_never_read(kind, window):
    """Every page outside every row's live range holds NaN (K/V payload,
    and the scales of int8 pages) under slot positions that look live:
    table blocks past a row's query or before its window, and pages no
    row owns.  The kernel never fetches them, so its output equals the
    reference on the clean pool and stays finite."""
    B, H, HKV, DH, BS, MB, P = 4, 8, 2, 16, 8, 6, 32
    lens, q_pos = [37, 30, 9, -1], [36, 20, 8, -1]
    q = jax.random.normal(KEY, (B, 1, H, DH))
    kp, vp, bt, ppos = build_pool(lens, num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    ks, vs, scale_kw, k_hi, v_hi = _stored_pool(kp, vp, kind)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    want = (ref.paged_attention_quant_ref(
                q, ks, vs, scale_kw["k_scales"], scale_kw["v_scales"],
                bt, ppos, q_pos, window=window) if scale_kw
            else ref.paged_attention_ref(q, k_hi, v_hi, bt, ppos, q_pos,
                                         window=window))
    live = set()
    for r, qp in enumerate(np.asarray(q_pos)):
        if qp >= 0:
            lo = 0 if window is None else max(0, qp - window + 1) // BS
            live |= set(np.asarray(bt)[r, lo:qp // BS + 1].tolist())
    dead = np.array(sorted(set(range(P)) - live))
    assert len(dead) > P - MB * B                 # some table blocks too
    ppos = ppos.at[dead].set(jnp.arange(20, 20 + BS))
    if scale_kw:
        scale_kw = {k: v.at[dead].set(jnp.nan) for k, v in scale_kw.items()}
    else:
        ks, vs = ks.at[dead].set(jnp.nan), vs.at[dead].set(jnp.nan)
    got = np.asarray(ops.paged_attention(q, ks, vs, bt, ppos, q_pos,
                                         window=window,
                                         interpret=TPU_INTERPRET,
                                         **scale_kw))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:3], np.asarray(want)[:3],
                               atol=KERNEL_ATOL, rtol=1e-4)


@pytest.mark.parametrize("kind", STORE_KINDS)
def test_paged_prefill_storage_parity(kind):
    """Chunked-prefill sweep: non-pow2 chunk with a padded row."""
    B, H, HKV, DH, BS, MB, P, LQ = 2, 4, 2, 8, 8, 4, 12, 7
    q = jax.random.normal(KEY, (B, LQ, H, DH))
    kp, vp, bt, ppos = build_pool([23, 11], num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    q_start = jnp.asarray([16, 6], jnp.int32)
    q_len = jnp.asarray([7, 5], jnp.int32)       # row 1: 2 padded queries
    ks, vs, scale_kw, k_hi, v_hi = _stored_pool(kp, vp, kind)
    got = ops.paged_prefill_attention(q, ks, vs, bt, ppos, q_start, q_len,
                                      interpret=True, **scale_kw)
    want = (ref.paged_prefill_attention_quant_ref(
                q, ks, vs, scale_kw["k_scales"], scale_kw["v_scales"],
                bt, ppos, q_start, q_len) if scale_kw
            else ref.paged_prefill_attention_ref(q, k_hi, v_hi, bt, ppos,
                                                 q_start, q_len))
    pristine = ref.paged_prefill_attention_ref(q, kp, vp, bt, ppos,
                                               q_start, q_len)
    bound = _storage_bound(q, kind, kp, vp, scale_kw) + KERNEL_ATOL
    for sl in (np.s_[0], np.s_[1, :5]):          # skip padded queries
        np.testing.assert_allclose(np.asarray(got)[sl],
                                   np.asarray(want)[sl],
                                   atol=KERNEL_ATOL, rtol=1e-4)
        err = np.abs(np.asarray(got)[sl] - np.asarray(pristine)[sl])
        assert err.max() <= bound, (kind, float(err.max()), bound)


def _sharded_build(lens, *, n_shards, bps, block_size, max_blocks, hkv,
                   dh, key):
    """ShardedKVPool layout: row r lives on shard r // (rows/n_shards);
    shard s owns blocks [s*bps, (s+1)*bps), local block 0 = trash."""
    num_blocks = n_shards * bps
    ks = jax.random.split(key, 2)
    kp = jax.random.normal(ks[0], (num_blocks, block_size, hkv, dh))
    vp = jax.random.normal(ks[1], (num_blocks, block_size, hkv, dh))
    bt = np.full((len(lens), max_blocks), -1, np.int32)
    ppos = np.full((num_blocks, block_size), -1, np.int32)
    free = {s: list(range(s * bps + 1, (s + 1) * bps))
            for s in range(n_shards)}
    rps = len(lens) // n_shards
    for r, n in enumerate(lens):
        if n < 0:
            continue
        nb = -(-n // block_size) if n else 0
        blocks = [free[r // rps].pop(0) for _ in range(nb)]
        bt[r, :nb] = blocks
        for t in range(n):
            ppos[blocks[t // block_size], t % block_size] = t
    return kp, vp, jnp.asarray(bt), jnp.asarray(ppos)


@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_sharded_paged_quantized_parity(kind):
    """shard_map'd decode + prefill kernels over quantized per-shard
    pages (degenerates to one shard on a single-device run; the
    devices=8 CI job exercises real shards via REPRO_TEST_DEVICES)."""
    data = 2 if jax.device_count() >= 2 else 1
    mesh = make_serve_mesh(data, 1)
    lens = [20, 9, 13, 5]
    kp, vp, bt, ppos = _sharded_build(lens, n_shards=data, bps=16 // data,
                                      block_size=8, max_blocks=4, hkv=2,
                                      dh=16, key=KEY)
    ks, vs, scale_kw, _, _ = _stored_pool(kp, vp, kind)
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 1, 8, 16))
    q_pos = jnp.asarray([19, 8, 12, 4], jnp.int32)
    got = ops.sharded_paged_attention(mesh, q, ks, vs, bt, ppos, q_pos,
                                      **scale_kw)
    want = ref.paged_attention_quant_ref(
        q, ks, vs, scale_kw["k_scales"], scale_kw["v_scales"],
        bt, ppos, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=KERNEL_ATOL, rtol=1e-4)
    pristine = ref.paged_attention_ref(q, kp, vp, bt, ppos, q_pos)
    bound = _storage_bound(q, kind, kp, vp, scale_kw) + KERNEL_ATOL
    err = np.abs(np.asarray(got) - np.asarray(pristine))
    assert err.max() <= bound, (kind, float(err.max()), bound)
    # chunked-prefill analogue on the same pool
    qc = jax.random.normal(jax.random.fold_in(KEY, 2), (4, 4, 8, 16))
    q_start = jnp.asarray([16, 5, 9, 1], jnp.int32)
    q_len = jnp.asarray([4, 4, 4, 4], jnp.int32)
    got = ops.sharded_paged_prefill_attention(mesh, qc, ks, vs, bt, ppos,
                                              q_start, q_len, **scale_kw)
    want = ref.paged_prefill_attention_quant_ref(
        qc, ks, vs, scale_kw["k_scales"], scale_kw["v_scales"],
        bt, ppos, q_start, q_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=KERNEL_ATOL, rtol=1e-4)


@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_quantized_bound_is_meaningful(kind):
    """Guard against a vacuous parity layer: the analytic bound must be
    a real constraint (within 100x of typical output magnitude), and the
    fp32/bf16 arms must NOT pass at the quantized arms' looser bound by
    construction — i.e. int8 error actually exceeds KERNEL_ATOL."""
    B, H, HKV, DH, BS, MB, P = 2, 4, 2, 16, 8, 4, 16
    q = jax.random.normal(KEY, (B, 1, H, DH)) * 3.0
    kp, vp, bt, ppos = build_pool([30, 17], num_blocks=P, block_size=BS,
                                  max_blocks=MB, hkv=HKV, dh=DH, key=KEY)
    q_pos = jnp.asarray([29, 16], jnp.int32)
    ks, vs, scale_kw, _, _ = _stored_pool(kp, vp, kind)
    got = ops.paged_attention(q, ks, vs, bt, ppos, q_pos,
                              interpret=True, **scale_kw)
    pristine = ref.paged_attention_ref(q, kp, vp, bt, ppos, q_pos)
    err = float(np.abs(np.asarray(got) - np.asarray(pristine)).max())
    bound = _storage_bound(q, kind, kp, vp, scale_kw)
    assert KERNEL_ATOL < err <= bound + KERNEL_ATOL
    assert bound <= 100.0 * float(np.abs(np.asarray(pristine)).max())
