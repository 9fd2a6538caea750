"""KV-cache quantization: kernels/kv_quant.py + the fused dequant decode
attention kernel (ref oracle vs Pallas interpret), incl. non-tile-multiple
shapes — the same class of bug as the d_ff=11008 quant_matmul assert."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import kv_quant as kvq
from repro.kernels import ops, ref


def _quant_cache(rng, b, s, hkv, d, bits, lengths=None):
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    return k, v, kvq.quantize_prefill({"k": k, "v": v}, lengths, bits)


# ------------------------------------------------------------ pack/unpack
@pytest.mark.parametrize("shape", [(6,), (3, 8), (2, 5, 4, 32)])
def test_pack4_roundtrip(rng, shape):
    codes = jnp.asarray(rng.integers(-8, 8, size=shape), jnp.int8)
    packed = kvq.pack4(codes)
    assert packed.dtype == jnp.uint8
    assert packed.shape == shape[:-1] + (shape[-1] // 2,)
    back = kvq.unpack4(packed)
    np.testing.assert_array_equal(np.asarray(back, np.int32),
                                  np.asarray(codes, np.int32))


# ------------------------------------------------------- quantize/dequant
@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_error_within_half_step(rng, bits):
    b, s, hkv, d = 2, 24, 3, 16
    k, v, qc = _quant_cache(rng, b, s, hkv, d, bits)
    kd = kvq.dequant_k(qc["kq"], qc["k_scale"], bits)
    vd = kvq.dequant_v(qc["vq"], qc["v_scale"], bits)
    # error bounded by half a step, per K channel / per V token
    k_bound = np.asarray(qc["k_scale"])[:, None, :, :] / 2 + 1e-6
    v_bound = np.asarray(qc["v_scale"])[..., None] / 2 + 1e-6
    assert (np.abs(np.asarray(kd - k)) <= k_bound).all()
    assert (np.abs(np.asarray(vd - v)) <= v_bound).all()


def test_k_scale_masks_garbage_rows(rng):
    """Right-pad garbage must not inflate the per-channel K grid — and
    therefore batched==solo quantization parity holds."""
    b, s, hkv, d = 1, 16, 2, 8
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    poisoned = k.at[:, 10:].set(1e3)          # garbage beyond length 10
    s1 = kvq.k_channel_scale(k, jnp.asarray([10]), 8)
    s2 = kvq.k_channel_scale(poisoned, jnp.asarray([10]), 8)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_quantize_prefill_stacked_leading_dim(rng):
    """Scan-stacked (n_repeats,)-leading cache leaves quantize the same as
    per-layer calls (the 'pat' splice path)."""
    L, b, s, hkv, d = 3, 2, 12, 2, 16
    k = jnp.asarray(rng.normal(size=(L, b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, b, s, hkv, d)), jnp.float32)
    lengths = jnp.asarray([7, 12], jnp.int32)
    stacked = kvq.quantize_prefill({"k": k, "v": v}, lengths, 8)
    for lyr in range(L):
        solo = kvq.quantize_prefill({"k": k[lyr], "v": v[lyr]}, lengths, 8)
        for key in ("kq", "k_scale", "vq", "v_scale"):
            np.testing.assert_array_equal(np.asarray(stacked[key][lyr]),
                                          np.asarray(solo[key]), err_msg=key)


def test_cache_bits_detection(rng):
    _, _, q8 = _quant_cache(rng, 1, 8, 1, 8, 8)
    _, _, q4 = _quant_cache(rng, 1, 8, 1, 8, 4)
    assert kvq.cache_bits(q8) == 8 and kvq.cache_bits(q4) == 4
    assert q8["kq"].dtype == jnp.int8 and q4["kq"].dtype == jnp.uint8
    assert q4["kq"].shape[-1] == 4                   # packed 2/byte


# --------------------------------------------- fused dequant attention
@pytest.mark.parametrize("s,d,hkv,group", [
    (56, 48, 2, 2),      # S_max and head_dim both non-128-multiples
    (37, 32, 1, 4),      # prime S_max -> single odd block
    (128, 64, 4, 1),     # aligned control
    (30, 34, 2, 2),      # even-but-odd head_dim (pack boundary)
])
@pytest.mark.parametrize("bits", [8, 4])
def test_kv_decode_attention_interpret_vs_ref(rng, s, d, hkv, group, bits):
    b, h = 2, hkv * group
    k, v, qc = _quant_cache(rng, b, s, hkv, d, bits)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    positions = jnp.asarray(rng.integers(0, s, size=(b,)), jnp.int32)
    got = ops.kv_cache_attention(q, qc["kq"], qc["k_scale"], qc["vq"],
                                 qc["v_scale"], positions, bits,
                                 impl="interpret")
    want = ops.kv_cache_attention(q, qc["kq"], qc["k_scale"], qc["vq"],
                                  qc["v_scale"], positions, bits, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_kv_decode_attention_explicit_small_block(rng):
    """A caller-forced block size that divides a non-tile-multiple S."""
    b, s, hkv, group, d = 1, 56, 2, 1, 48
    _, _, qc = _quant_cache(rng, b, s, hkv, d, 8)
    q = jnp.asarray(rng.normal(size=(b, hkv * group, d)), jnp.float32)
    positions = jnp.asarray([s - 1], jnp.int32)
    got = ops.kv_cache_attention(q, qc["kq"], qc["k_scale"], qc["vq"],
                                 qc["v_scale"], positions, 8,
                                 impl="interpret", bs=8)
    want = ops.kv_cache_attention(q, qc["kq"], qc["k_scale"], qc["vq"],
                                  qc["v_scale"], positions, 8, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("pos", [0, 5, 55])
def test_kv_decode_attention_mask_positions(rng, pos):
    """Rows beyond the position must not contribute: poisoning them leaves
    the output unchanged (the garbage-rows-unread argument, kernel-level)."""
    b, s, hkv, d = 1, 56, 2, 32
    k, v, qc = _quant_cache(rng, b, s, hkv, d, 8)
    q = jnp.asarray(rng.normal(size=(b, hkv, d)), jnp.float32)
    positions = jnp.asarray([pos], jnp.int32)
    poisoned = dict(qc)
    poisoned["kq"] = qc["kq"].at[:, pos + 1:].set(127)
    poisoned["vq"] = qc["vq"].at[:, pos + 1:].set(127)
    poisoned["v_scale"] = qc["v_scale"].at[:, pos + 1:].set(1e3)
    for impl in ("ref", "interpret"):
        a = ops.kv_cache_attention(q, qc["kq"], qc["k_scale"], qc["vq"],
                                   qc["v_scale"], positions, 8, impl=impl)
        bb = ops.kv_cache_attention(q, poisoned["kq"], qc["k_scale"],
                                    poisoned["vq"], poisoned["v_scale"],
                                    positions, 8, impl=impl)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


@pytest.mark.parametrize("impl", ["interpret", "ref"])
@pytest.mark.parametrize("bits", [8, 4])
def test_kv_decode_attention_reads_a_layer_stack_by_index(rng, bits, impl):
    """Stacked (L, B, S, Hkv, Dp) codes and (L, B, S, Hkv) V scales read
    at a layer index give, bit for bit, what the call on that layer's
    slice gives — the decode layer scan's carried read.  Slot 2's
    position is past S_max, as an evicted slot's is."""
    n_layers, b, s, hkv, group, d = 3, 3, 40, 2, 2, 32
    layers = [_quant_cache(rng, b, s, hkv, d, bits)[2]
              for _ in range(n_layers)]
    stack = {k: jnp.stack([c[k] for c in layers])
             for k in ("kq", "vq", "v_scale")}
    q = jnp.asarray(rng.normal(size=(b, hkv * group, d)), jnp.float32)
    positions = jnp.asarray([0, 23, s + 5], jnp.int32)
    for lyr, c in enumerate(layers):
        got = ops.kv_cache_attention(
            q, stack["kq"], c["k_scale"], stack["vq"], stack["v_scale"],
            positions, bits, impl=impl, layer=jnp.int32(lyr), bs=8)
        want = ops.kv_cache_attention(
            q, c["kq"], c["k_scale"], c["vq"], c["v_scale"], positions,
            bits, impl=impl, bs=8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------- paged decode attention
def _paged_cache(rng, b, hkv, d, bits, lengths, page, n_pages, pool_extra=2,
                 poison=None):
    """Build a contiguous quant cache and scatter it into page pools via
    disjoint per-slot tables; returns (contiguous qc, pools, tbl)."""
    s_virt = n_pages * page
    k = jnp.asarray(rng.normal(size=(b, s_virt, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s_virt, hkv, d)), jnp.float32)
    qc = kvq.quantize_prefill({"k": k, "v": v}, jnp.asarray(lengths), bits)
    p_phys = b * n_pages + pool_extra
    dp = qc["kq"].shape[-1]
    fill_c = 127 if poison is None else poison[0]
    fill_s = 0.0 if poison is None else poison[1]
    kq_pool = jnp.full((p_phys, page, hkv, dp), fill_c, qc["kq"].dtype)
    vq_pool = jnp.full((p_phys, page, hkv, dp), fill_c, qc["vq"].dtype)
    vs_pool = jnp.full((p_phys, page, hkv), fill_s, jnp.float32)
    tbl = jnp.asarray([[i * n_pages + j for j in range(n_pages)]
                      for i in range(b)], jnp.int32)
    for i in range(b):
        for j in range(n_pages):
            sl = slice(j * page, (j + 1) * page)
            kq_pool = kq_pool.at[tbl[i, j]].set(qc["kq"][i, sl])
            vq_pool = vq_pool.at[tbl[i, j]].set(qc["vq"][i, sl])
            vs_pool = vs_pool.at[tbl[i, j]].set(qc["v_scale"][i, sl])
    return qc, (kq_pool, vq_pool, vs_pool), tbl


@pytest.mark.parametrize("lengths,page,n_pages", [
    ((37, 53), 16, 4),   # non-page-multiple lengths, mid-page positions
    ((1, 64), 16, 4),    # first-row-only and exactly-full
    ((23, 9), 8, 5),     # non-16 page size
])
@pytest.mark.parametrize("bits", [8, 4])
def test_paged_decode_matches_contiguous_and_interpret(rng, lengths, page,
                                                       n_pages, bits):
    """The paged ref oracle is BIT-exact with the contiguous oracle (the
    differential contract serve parity builds on), and the Pallas paged
    kernel (interpret) matches the oracle through the block-table
    indirection — including last-partial-page masking (positions sit
    mid-page)."""
    b, hkv, group, d = len(lengths), 2, 2, 32
    qc, (kqp, vqp, vsp), tbl = _paged_cache(rng, b, hkv, d, bits, lengths,
                                            page, n_pages)
    q = jnp.asarray(rng.normal(size=(b, hkv * group, d)), jnp.float32)
    positions = jnp.asarray(lengths, jnp.int32) - 1
    want = ops.kv_cache_attention(q, qc["kq"], qc["k_scale"], qc["vq"],
                                  qc["v_scale"], positions, bits, impl="ref")
    got_ref = ops.paged_kv_cache_attention(q, kqp, qc["k_scale"], vqp, vsp,
                                           tbl, positions, bits, impl="ref")
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(want))
    got_int = ops.paged_kv_cache_attention(q, kqp, qc["k_scale"], vqp, vsp,
                                           tbl, positions, bits,
                                           impl="interpret")
    np.testing.assert_allclose(np.asarray(got_int), np.asarray(got_ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("bits", [8, 4])
def test_paged_decode_poisoned_free_pages(rng, bits):
    """Fill every UNMAPPED physical page with poison (saturated codes and
    NaN V scales) — decode output must be bit-identical: free pages are
    only reachable through masked positions or not at all."""
    b, hkv, d, page, n_pages = 2, 2, 32, 16, 3
    lengths = (20, 41)
    qc, pools, tbl = _paged_cache(np.random.default_rng(3), b, hkv, d, bits,
                                  lengths, page, n_pages, pool_extra=3)
    qp, pools_poison, _ = _paged_cache(np.random.default_rng(3), b, hkv, d,
                                       bits, lengths, page, n_pages,
                                       pool_extra=3, poison=(127, np.nan))
    # same seed -> mapped pages identical; only the free-page fill differs
    q = jnp.asarray(np.random.default_rng(1).normal(size=(b, hkv * 2, d)),
                    jnp.float32)
    positions = jnp.asarray(lengths, jnp.int32) - 1
    for impl in ("ref", "interpret"):
        a = ops.paged_kv_cache_attention(q, pools[0], qc["k_scale"],
                                         pools[1], pools[2], tbl, positions,
                                         bits, impl=impl)
        bb = ops.paged_kv_cache_attention(q, pools_poison[0], qp["k_scale"],
                                          pools_poison[1], pools_poison[2],
                                          tbl, positions, bits, impl=impl)
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb),
                                      err_msg=impl)


def test_paged_decode_stale_table_entries_unread(rng):
    """Table entries beyond a slot's position (stale ids / -1 sentinel)
    must not contribute — remapping them arbitrarily leaves the output
    unchanged."""
    b, hkv, d, page, n_pages = 1, 2, 32, 16, 4
    qc, (kqp, vqp, vsp), tbl = _paged_cache(rng, b, hkv, d, 8, (17,), page,
                                            n_pages)
    positions = jnp.asarray([16], jnp.int32)     # only pages 0-1 live
    q = jnp.asarray(rng.normal(size=(b, hkv, d)), jnp.float32)
    stale = tbl.at[0, 2].set(0).at[0, 3].set(-1)
    for impl in ("ref", "interpret"):
        a = ops.paged_kv_cache_attention(q, kqp, qc["k_scale"], vqp, vsp,
                                         tbl, positions, 8, impl=impl)
        bb = ops.paged_kv_cache_attention(q, kqp, qc["k_scale"], vqp, vsp,
                                          stale, positions, 8, impl=impl)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb),
                                      err_msg=impl)


def test_paged_write_row_drop_semantics(rng):
    """paged_write_row drops (never redirects) writes through unmapped
    table entries: -1 sentinel pages and out-of-range positions — the
    page-isolation guarantee a budget-overrun decode chunk relies on."""
    pool = jnp.zeros((4, 4, 2, 3))
    tbl = jnp.asarray([[2, -1], [3, 1]], jnp.int32)
    new = jnp.asarray(rng.normal(size=(2, 1, 2, 3)), jnp.float32)
    # slot 0 writes pos 5 -> logical page 1 -> UNMAPPED (-1): dropped
    # slot 1 writes pos 6 -> page 1 -> phys 1: lands
    out = kvq.paged_write_row(pool, new, jnp.asarray([[5], [6]], jnp.int32),
                              tbl)
    assert float(jnp.abs(out[0]).sum()) == 0.0   # clamp target untouched
    assert float(jnp.abs(out[2]).sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(out[1, 2]),
                                  np.asarray(new[1, 0]))
    # out-of-range position (>= n*page): dropped entirely
    out = kvq.paged_write_row(pool, new, jnp.asarray([[8], [9]], jnp.int32),
                              tbl)
    assert float(jnp.abs(out).sum()) == 0.0


def test_gather_pages_roundtrip(rng):
    pool = jnp.asarray(rng.normal(size=(6, 4, 2, 3)), jnp.float32)
    tbl = jnp.asarray([[5, 0, 2], [1, 1, 4]], jnp.int32)
    got = np.asarray(kvq.gather_pages(pool, tbl))
    for i in range(2):
        for j in range(3):
            np.testing.assert_array_equal(got[i, j * 4:(j + 1) * 4],
                                          np.asarray(pool[tbl[i, j]]))
    assert kvq.page_count(17, 16) == 2 and kvq.page_count(16, 16) == 1


def test_kv_decode_attention_close_to_full_precision(rng):
    """int8 quantized-cache attention tracks exact f32 attention within the
    quantization error budget (sanity: the lossy path is NEAR, the exact
    tests above pin the semantics)."""
    b, s, hkv, group, d = 2, 48, 2, 2, 32
    h = hkv * group
    k, v, qc = _quant_cache(rng, b, s, hkv, d, 8)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    positions = jnp.full((b,), s - 1, jnp.int32)
    got = ops.kv_cache_attention(q, qc["kq"], qc["k_scale"], qc["vq"],
                                 qc["v_scale"], positions, 8, impl="ref")
    kk = jnp.repeat(k, group, axis=2).swapaxes(1, 2)     # (B,H,S,D)
    vv = jnp.repeat(v, group, axis=2).swapaxes(1, 2)
    want = ref.attention(q[:, :, None, :], kk, vv, causal=False)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)
