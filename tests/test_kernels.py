"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (block_gather, chunked_prefill_attention,
                           kv_block_dequantize, kv_block_quantize,
                           paged_decode_attention)
from repro.kernels.paged_attention import pages_per_block
from repro.kernels.ref import (block_gather_ref,
                               chunked_prefill_attention_ref,
                               kv_block_dequantize_ref,
                               kv_block_quantize_ref,
                               paged_decode_attention_ref)

pytestmark = pytest.mark.kernel

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


def _decode_lens(case, b, page, maxp, span):
    """Per-row lengths for a sweep case; ``span`` is the kernel's tokens
    per grid step (pages per block times the page size)."""
    rng = np.random.default_rng(0)
    lens = rng.integers(1, maxp * page + 1, b)
    if case == "len0":                      # every other row is empty
        lens[::2] = 0
    elif case == "block_edges":             # on a block's end, one past it
        lens = np.resize([span, span + 1, span - 1, 1], b)
    elif case == "nan_page_tail":           # odd: a last token's lane
        lens = lens | 1                     # partner lies past the length
    return jnp.asarray(np.minimum(lens, maxp * page), jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,hd,page,maxp,case", [
    pytest.param(2, 4, 4, 32, 16, 4, "random",
                 id="2-4-4-32-16-4"),                # MHA (G=1)
    pytest.param(3, 8, 2, 64, 16, 5, "random",
                 id="3-8-2-64-16-5"),                # GQA G=4
    pytest.param(1, 16, 2, 16, 8, 8, "random",
                 id="1-16-2-16-8-8"),                # G=8, small pages
    pytest.param(4, 6, 6, 128, 32, 2, "random",
                 id="4-6-6-128-32-2"),               # head_dim 128
    pytest.param(5, 8, 2, 64, 16, 6, "len0", id="len0"),
    # maxp 12 is not a multiple of its 8 pages per block
    pytest.param(4, 8, 2, 64, 16, 12, "block_edges", id="block_edges"),
    pytest.param(3, 8, 2, 64, 16, 7, "nan_tail", id="nan_tail"),
    pytest.param(3, 8, 2, 64, 16, 7, "nan_page_tail", id="nan_page_tail"),
    # Qwen1.5-0.5B's attention widths at a decode bucket's table width
    pytest.param(3, 16, 16, 64, 16, 96, "random", id="mha_cell_widths"),
    pytest.param(3, 28, 4, 128, 16, 6, "random",
                 id="gqa_g7"),                       # Qwen2-7B's 28/4
])
def test_paged_decode_attention_sweep(dtype, b, h, hkv, hd, page, maxp,
                                      case):
    ks = jax.random.split(KEY, 4)
    P = maxp * b + 3
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    kp = jax.random.normal(ks[1], (P, hkv, page, hd), dtype)
    vp = jax.random.normal(ks[2], (P, hkv, page, hd), dtype)
    bt = jax.random.randint(ks[3], (b, maxp), 0, P)
    span = page * pages_per_block(maxp, hkv, page, hd, dtype)
    lens = _decode_lens(case, b, page, maxp, span)
    if case == "nan_tail":
        bt = bt % (P - 3)                     # the last 3 pages become NaN
    if case == "nan_page_tail":               # a page of its own per entry
        bt = jax.random.permutation(ks[3], P)[:b * maxp].reshape(b, maxp)
    ref = paged_decode_attention_ref(q, kp, vp, bt, lens)
    if case == "nan_tail":
        # table entries past each row's last page name pages of NaN: the
        # kernel must neither read them nor let a stale buffer reach the
        # output, which must equal the reference over finite pages
        nan_pages = jnp.arange(P - 3, P)
        kp = kp.at[nan_pages].set(jnp.nan)
        vp = vp.at[nan_pages].set(jnp.nan)
        past = jnp.arange(maxp)[None, :] * page >= lens[:, None]
        bt = jnp.where(past, nan_pages[jnp.arange(maxp) % 3][None, :], bt)
        assert bool(past.any())
    if case == "nan_page_tail":
        # the unwritten tail of each row's last page is NaN: a valid
        # token's score must not take in its lane partner past the length
        last = bt[jnp.arange(b), (lens - 1) // page]
        tail = jnp.arange(page)[None, :] >= (lens % page)[:, None]
        mask = jnp.zeros((P, page), bool).at[last].set(tail)
        kp = jnp.where(mask[:, None, :, None], jnp.nan, kp)
        vp = jnp.where(mask[:, None, :, None], jnp.nan, vp)
    out = paged_decode_attention(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_paged_decode_length_edge_cases():
    """len == 1 and len == full capacity."""
    b, h, hkv, hd, page, maxp = 2, 4, 2, 16, 8, 3
    ks = jax.random.split(KEY, 4)
    P = 8
    q = jax.random.normal(ks[0], (b, h, hd))
    kp = jax.random.normal(ks[1], (P, hkv, page, hd))
    vp = jax.random.normal(ks[2], (P, hkv, page, hd))
    bt = jax.random.randint(ks[3], (b, maxp), 0, P)
    lens = jnp.asarray([1, maxp * page], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, lens)
    ref = paged_decode_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,smax,h,hkv,hd,kvb", [
    (2, 8, 64, 4, 2, 32, 16),
    (1, 16, 128, 8, 8, 16, 32),
    (3, 4, 40, 6, 2, 64, 16),   # smax not a multiple of kvb
])
def test_chunked_prefill_sweep(dtype, b, sq, smax, h, hkv, hd, kvb):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd), dtype)
    kc = jax.random.normal(ks[1], (b, hkv, smax, hd), dtype)
    vc = jax.random.normal(ks[2], (b, hkv, smax, hd), dtype)
    rng = np.random.default_rng(1)
    lens = jnp.asarray(rng.integers(sq, smax + 1, b), jnp.int32)
    out = chunked_prefill_attention(q, kc, vc, lens, kv_block=kvb)
    ref = chunked_prefill_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_chunked_prefill_fresh_prompt():
    """cache_len == Sq: pure prefill with no prefix (causal within chunk)."""
    b, sq, h, hkv, hd = 2, 12, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd))
    kc = jax.random.normal(ks[1], (b, hkv, sq, hd))
    vc = jax.random.normal(ks[2], (b, hkv, sq, hd))
    lens = jnp.full((b,), sq, jnp.int32)
    out = chunked_prefill_attention(q, kc, vc, lens, kv_block=8)
    ref = chunked_prefill_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_gather(dtype):
    pool = jax.random.normal(KEY, (32, 2, 16, 8), dtype)    # (P, Hkv, bs, hd)
    idx = jnp.asarray([3, 31, 0, 3, 17], jnp.int32)
    out = block_gather(pool, idx)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(block_gather_ref(pool, idx)))


@pytest.mark.parametrize("n,layers,bs,hkv,hd,amp", [
    (3, 2, 8, 2, 16, 3.0),
    (1, 4, 16, 2, 64, 0.02),    # tiny magnitudes
    (7, 2, 4, 1, 8, 50.0),      # large magnitudes
])
def test_kv_quant_bitwise_vs_ref(n, layers, bs, hkv, hd, amp):
    """Quantize AND dequantize kernels are bitwise-equal to the oracles
    (elementwise ops + exact reductions only)."""
    x = jax.random.normal(KEY, (n, layers, 2, hkv, bs, hd)) * amp
    x = x.at[0, 0].set(jnp.zeros_like(x[0, 0]))   # all-zero plane: scale 0
    vals, scales = kv_block_quantize(x)
    vr, sr = kv_block_quantize_ref(x)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(scales), np.asarray(sr))
    assert vals.dtype == jnp.int8 and scales.shape == (n, layers, 2)
    deq = kv_block_dequantize(vals, scales)
    np.testing.assert_array_equal(np.asarray(deq),
                                  np.asarray(kv_block_dequantize_ref(vr,
                                                                     sr)))


def test_kv_quant_roundtrip_error_bound():
    """The documented int8 bound: per element |x - deq(quant(x))| <=
    scale/2 of its (block, layer, k|v) plane, zero planes exact."""
    x = jax.random.normal(KEY, (4, 3, 2, 2, 8, 16)) * 7.0
    x = x.at[1].set(jnp.zeros_like(x[1]))
    vals, scales = kv_block_quantize(x)
    deq = np.asarray(kv_block_dequantize(vals, scales))
    err = np.abs(deq - np.asarray(x))
    bound = np.asarray(scales)[..., None, None, None] / 2.0
    assert (err <= bound).all()
    np.testing.assert_array_equal(deq[1], np.zeros_like(deq[1]))
    # extrema survive the roundtrip at full scale: absmax maps to +-127
    flat = np.abs(np.asarray(x)).reshape(4 * 3 * 2, -1)
    amax_q = np.abs(np.asarray(vals)).reshape(4 * 3 * 2, -1).max(axis=1)
    assert (amax_q[flat.max(axis=1) > 0] == 127).all()


def test_kernel_consistency_with_model_decode():
    """Paged kernel result == model's dense decode_attention on the same
    logical KV (the engine relies on this)."""
    from repro.models.layers import decode_attention as model_decode
    b, h, hkv, hd, page, maxp = 2, 4, 2, 16, 8, 4
    ks = jax.random.split(KEY, 4)
    P = b * maxp + 1
    q = jax.random.normal(ks[0], (b, h, hd))
    kp = jax.random.normal(ks[1], (P, hkv, page, hd))
    vp = jax.random.normal(ks[2], (P, hkv, page, hd))
    bt = jnp.arange(1, 1 + b * maxp, dtype=jnp.int32).reshape(b, maxp)
    lens = jnp.asarray([13, 29], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, lens)
    # build the contiguous (token-major) equivalent of the head-major pages
    k_lin = kp[bt].transpose(0, 1, 3, 2, 4).reshape(b, maxp * page, hkv, hd)
    v_lin = vp[bt].transpose(0, 1, 3, 2, 4).reshape(b, maxp * page, hkv, hd)
    ref = model_decode(q[:, None], k_lin, v_lin, lens)[:, 0]
    np.testing.assert_allclose(out, ref, atol=2e-5)
