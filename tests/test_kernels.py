"""Per-kernel correctness: Pallas (interpret=True) vs the pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.paged_attention import (
    fused_paged_attention_pallas,
    mla_fused_paged_attention_pallas,
    mla_paged_attention_pallas,
    paged_attention_pallas,
)
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.wkv6 import wkv6_pallas
from repro.kv.layout import (deinterleave_kv, fuse_mla, interleave_kv,
                             split_mla)

# every test here executes real Pallas kernel bodies through the CPU
# interpreter — select with `-m pallas_interpret`, skip with
# `-m "not pallas_interpret"`; they run (and pass) under plain tier-1.
pytestmark = [pytest.mark.pallas_interpret, pytest.mark.kernels]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 32), (2, 7, 96), (1, 129, 64),
                                   (3, 5, 2, 48)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, shape).astype(dtype)
    sc = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],))
    got = rmsnorm_pallas(x, sc, interpret=True, block_rows=32)
    want = ref.rmsnorm_ref(x, sc)
    assert got.dtype == want.dtype
    assert_allclose(np.asarray(got, np.float32),
                    np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,skv,hq,hkv", [
    (16, 16, 4, 4),      # MHA, aligned
    (37, 37, 4, 2),      # GQA 2:1, ragged
    (8, 40, 4, 1),       # MQA, chunked-prefill style (q_offset)
    (64, 64, 8, 8),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 8), (False, 0)])
def test_flash_attention(sq, skv, hq, hkv, causal, window):
    B, D = 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, sq, hq, D))
    k = jax.random.normal(ks[1], (B, skv, hkv, D))
    v = jax.random.normal(ks[2], (B, skv, hkv, D))
    q_off = skv - sq
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=q_off, blk_q=16, blk_k=16,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_off)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("bias_shape", [(2, 4, 37, 48), (1, 4, 37, 48),
                                        (2, 1, 37, 48), (37, 48)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_bias(bias_shape, causal):
    """Additive attention bias (ALiBi/relative-position style), every
    broadcast rank the ref accepts, ragged blocks + chunked prefill."""
    B, Sq, Skv, Hq, Hkv, D = 2, 37, 48, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D))
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D))
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D))
    bias = jax.random.normal(ks[3], bias_shape) * 2.0
    q_off = Skv - Sq
    got = flash_attention_pallas(q, k, v, causal=causal, q_offset=q_off,
                                 bias=bias, blk_q=16, blk_k=16,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, q_offset=q_off,
                             bias=bias)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)
    # sanity: the bias actually changed the result
    plain = ref.attention_ref(q, k, v, causal=causal, q_offset=q_off)
    assert not np.allclose(np.asarray(want), np.asarray(plain))


def test_flash_attention_bias_with_segments():
    """bias and segment_ids compose: mask first, bias on masked logits."""
    B, S, Hq, Hkv, D = 2, 32, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(13), 4)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    bias = jax.random.normal(ks[3], (B, Hq, S, S))
    seg = np.full((B, S), -1, np.int32)
    seg[0, :12], seg[0, 12:28] = 0, 1
    seg[1, :20], seg[1, 20:30] = 0, 1
    seg = jnp.asarray(seg)
    got = flash_attention_pallas(q, k, v, causal=True, segment_ids=seg,
                                 bias=bias, blk_q=16, blk_k=16,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, segment_ids=seg,
                             bias=bias)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 12),
                                           (False, 0)])
def test_flash_attention_segment_ids(causal, window):
    """Sequence-packed rows: attention restricted to same-segment pairs
    (ragged segment layout per batch row, -1 tail pads)."""
    B, S, Hq, Hkv, D = 2, 48, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    seg = np.full((B, S), -1, np.int32)
    seg[0, :10], seg[0, 10:30], seg[0, 30:44] = 0, 1, 2
    seg[1, :25], seg[1, 25:40] = 0, 1
    seg = jnp.asarray(seg)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 segment_ids=seg, blk_q=16, blk_k=16,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             segment_ids=seg)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)
    # sanity: the segment mask actually changed the result
    plain = ref.attention_ref(q, k, v, causal=causal, window=window)
    assert not np.allclose(np.asarray(want), np.asarray(plain))


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 12)])
def test_flash_attention_segment_ids_chunked_prefill(causal, window):
    """Chunked prefill packs too: segment_ids label the KV axis and the
    q chunk's labels are the slice at q_offset.  A SHARED (-2) prefix
    block is attendable by every segment."""
    B, Sq, Skv, Hq, Hkv, D = 2, 16, 48, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D))
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D))
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D))
    seg = np.full((B, Skv), -1, np.int32)
    seg[0, :6] = ref.SHARED_SEGMENT_ID          # shared modality prefix
    seg[0, 6:24], seg[0, 24:44] = 0, 1
    seg[1, :30], seg[1, 30:48] = 0, 1
    seg = jnp.asarray(seg)
    q_off = Skv - Sq
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=q_off, segment_ids=seg,
                                 blk_q=16, blk_k=16, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_off, segment_ids=seg)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)
    # the segment mask binds, and the shared prefix really is attended:
    # scrubbing it changes row 0's output
    plain = ref.attention_ref(q, k, v, causal=causal, window=window,
                              q_offset=q_off)
    assert not np.allclose(np.asarray(want), np.asarray(plain))
    if window == 0:  # a binding window already hides the distant prefix
        seg_noshare = seg.at[0, :6].set(-1)
        scrubbed = ref.attention_ref(q, k, v, causal=causal, window=window,
                                     q_offset=q_off,
                                     segment_ids=seg_noshare)
        assert not np.allclose(np.asarray(want)[0], np.asarray(scrubbed)[0])


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    B, S, H, D = 1, 33, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, D)).astype(dtype)
    got = flash_attention_pallas(q, k, v, blk_q=16, blk_k=16, interpret=True)
    want = ref.attention_ref(q, k, v)
    assert got.dtype == dtype
    assert_allclose(np.asarray(got, np.float32),
                    np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv,window", [(4, 2, 0), (8, 8, 0), (4, 1, 12),
                                           (4, 2, 5)])
def test_paged_attention(hq, hkv, window):
    B, D, P, page, MP = 3, 32, 24, 8, 5
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, hq, D))
    kp = jax.random.normal(ks[1], (P, page, hkv, D))
    vp = jax.random.normal(ks[2], (P, page, hkv, D))
    tables = jnp.array([[3, 5, 1, -1, -1],
                        [0, 2, 7, 9, -1],
                        [11, 12, 13, 14, 15]], jnp.int32)
    lengths = jnp.array([19, 26, 40], jnp.int32)
    got = paged_attention_pallas(q, kp, vp, tables, lengths, page_size=page,
                                 window=window, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths,
                                   page_size=page, window=window)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


def test_paged_vs_dense_decode():
    """Paged attention == dense decode attention on the same KV."""
    B, S, Hq, Hkv, D, page = 2, 24, 4, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    lengths = jnp.array([17, 24], jnp.int32)
    # build pools from the dense cache
    kp = k.reshape(B * S // page, page, Hkv, D)
    vp = v.reshape(B * S // page, page, Hkv, D)
    tables = jnp.array([[0, 1, 2], [3, 4, 5]], jnp.int32)
    got = paged_attention_pallas(q, kp, vp, tables, lengths, page_size=page,
                                 interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# pipelined fused-pool paged attention (multi-buffered page DMA)
# ---------------------------------------------------------------------------

def _fused_inputs(key, B, hq, hkv, D, P, page):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, hq, D))
    kp = jax.random.normal(ks[1], (P, page, hkv, D))
    vp = jax.random.normal(ks[2], (P, page, hkv, D))
    return q, kp, vp, interleave_kv(kp, vp)


def test_kv_layout_roundtrip():
    """interleave/deinterleave and fuse/split are exact inverses — the one
    layout contract every producer/consumer shares."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    k = jax.random.normal(ks[0], (6, 8, 3, 16))
    v = jax.random.normal(ks[1], (6, 8, 3, 16))
    kv = interleave_kv(k, v)
    assert kv.shape == (6, 8, 6, 16)
    # head axis is [K0, V0, K1, V1, ...]
    assert_allclose(np.asarray(kv[..., 0, :]), np.asarray(k[..., 0, :]))
    assert_allclose(np.asarray(kv[..., 1, :]), np.asarray(v[..., 0, :]))
    k2, v2 = deinterleave_kv(kv)
    assert_allclose(np.asarray(k2), np.asarray(k))
    assert_allclose(np.asarray(v2), np.asarray(v))
    ckv = jax.random.normal(ks[2], (6, 8, 16))
    kr = jax.random.normal(ks[3], (6, 8, 4))
    c2, r2 = split_mla(fuse_mla(ckv, kr), 16)
    assert_allclose(np.asarray(c2), np.asarray(ckv))
    assert_allclose(np.asarray(r2), np.asarray(kr))


@pytest.mark.parametrize("hq,hkv,window", [(4, 2, 0), (8, 8, 0), (4, 1, 12),
                                           (4, 2, 5)])
@pytest.mark.parametrize("nb", [1, 2, 4])
def test_fused_paged_attention_parity(hq, hkv, window, nb):
    """Pipelined fused kernel == legacy split kernel == jnp oracle across
    buffer depths, GQA group sizes, window>0, and page counts {0, 1, many}
    (lengths 0 / 5 / 40)."""
    B, D, P, page = 4, 32, 24, 8
    q, kp, vp, kv = _fused_inputs(jax.random.PRNGKey(21), B, hq, hkv, D,
                                  P, page)
    tables = jnp.array([[-1, -1, -1, -1, -1],
                        [3, -1, -1, -1, -1],
                        [0, 2, 7, 9, -1],
                        [11, 12, 13, 14, 15]], jnp.int32)
    lengths = jnp.array([0, 5, 26, 40], jnp.int32)
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths,
                                   page_size=page, window=window)
    fused_want = ref.fused_paged_attention_ref(q, kv, tables, lengths,
                                               page_size=page, window=window)
    assert_allclose(np.asarray(fused_want), np.asarray(want), rtol=1e-6,
                    atol=1e-6)
    legacy = paged_attention_pallas(q, kp, vp, tables, lengths,
                                    page_size=page, window=window,
                                    interpret=True)
    got = fused_paged_attention_pallas(q, kv, tables, lengths,
                                       page_size=page, window=window,
                                       num_buffers=nb, interpret=True)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert_allclose(np.asarray(got), np.asarray(legacy), rtol=1e-5,
                    atol=1e-5)


def test_fused_paged_vs_dense_decode():
    """Pipelined fused kernel == dense decode attention on the same KV."""
    B, S, Hq, Hkv, D, page = 2, 24, 4, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(22), 3)
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    lengths = jnp.array([17, 24], jnp.int32)
    kv = interleave_kv(k.reshape(B * S // page, page, Hkv, D),
                       v.reshape(B * S // page, page, Hkv, D))
    tables = jnp.array([[0, 1, 2], [3, 4, 5]], jnp.int32)
    got = fused_paged_attention_pallas(q, kv, tables, lengths,
                                       page_size=page, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_bitwise_stable_across_buffer_depths():
    """num_buffers is a pure DMA-scheduling knob: the page-visit order and
    online softmax are depth-independent, so outputs must be BITWISE equal
    across depths {1, 2, 4} — for both the GQA and the MLA kernel."""
    B, hq, hkv, D, P, page = 3, 8, 2, 32, 24, 8
    q, _, _, kv = _fused_inputs(jax.random.PRNGKey(23), B, hq, hkv, D,
                                P, page)
    tables = jnp.array([[3, 5, 1, -1, -1],
                        [0, 2, 7, 9, -1],
                        [11, 12, 13, 14, 15]], jnp.int32)
    lengths = jnp.array([19, 26, 40], jnp.int32)
    outs = [np.asarray(fused_paged_attention_pallas(
        q, kv, tables, lengths, page_size=page, num_buffers=nb,
        interpret=True)) for nb in (1, 2, 4)]
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[1], outs[2])

    H, r, rd = 4, 16, 8
    ql, qr, ckv, kr = _mla_inputs(jax.random.PRNGKey(24), B, H, r, rd,
                                  P, page, 5)
    mkv = fuse_mla(ckv, kr)
    mouts = [np.asarray(mla_fused_paged_attention_pallas(
        ql, qr, mkv, tables, lengths, page_size=page, scale=0.2,
        num_buffers=nb, interpret=True)) for nb in (1, 2, 4)]
    assert np.array_equal(mouts[0], mouts[1])
    assert np.array_equal(mouts[1], mouts[2])


@pytest.mark.parametrize("nb", [1, 2, 4])
def test_mla_fused_paged_attention_parity(nb):
    """Pipelined fused-latent MLA kernel == legacy split kernel == oracle,
    including a zero-length padding row."""
    B, H, r, rd, P, page = 4, 4, 16, 8, 24, 8
    ql, qr, ckv, kr = _mla_inputs(jax.random.PRNGKey(25), B, H, r, rd,
                                  P, page, 5)
    mkv = fuse_mla(ckv, kr)
    tables = jnp.array([[-1, -1, -1, -1, -1],
                        [3, -1, -1, -1, -1],
                        [0, 2, 7, 9, -1],
                        [11, 12, 13, 14, 15]], jnp.int32)
    lengths = jnp.array([0, 5, 26, 40], jnp.int32)
    scale = 1.0 / ((r + rd) ** 0.5)
    want = ref.mla_paged_attention_ref(ql, qr, ckv, kr, tables, lengths,
                                       page_size=page, scale=scale)
    fused_want = ref.mla_fused_paged_attention_ref(
        ql, qr, mkv, tables, lengths, page_size=page, scale=scale)
    assert_allclose(np.asarray(fused_want), np.asarray(want), rtol=1e-6,
                    atol=1e-6)
    legacy = mla_paged_attention_pallas(ql, qr, ckv, kr, tables, lengths,
                                        page_size=page, scale=scale,
                                        interpret=True)
    got = mla_fused_paged_attention_pallas(ql, qr, mkv, tables, lengths,
                                           page_size=page, scale=scale,
                                           num_buffers=nb, interpret=True)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert_allclose(np.asarray(got), np.asarray(legacy), rtol=1e-5,
                    atol=1e-5)


def test_paged_zero_length_rows_emit_zeros_not_page0_garbage():
    """REGRESSION (fully-masked-row bug): a row with lengths[b] == 0 — a
    padding row in the fixed-shape serve dispatch — left m at -1e30, so
    p = exp(s - m) = exp(0) = 1 for every masked position and the flush
    emitted the MEAN OF PAGE 0's stale contents.  Pre-fix, every kernel
    and both paged references returned ~1e4 here (page 0 is poisoned to
    make the old behavior unmissable); post-fix they must return exact
    zeros.  Covers the legacy split kernels, the pipelined fused kernels
    at every buffer depth, and all four references."""
    B, hq, hkv, D, P, page = 2, 4, 2, 16, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(26), 3)
    q = jax.random.normal(ks[0], (B, hq, D))
    # page 0 poisoned: the old bug averaged these values into the output
    kp = jnp.full((P, page, hkv, D), 1e4)
    vp = jnp.full((P, page, hkv, D), 1e4)
    kv = interleave_kv(kp, vp)
    tables = jnp.array([[-1, -1], [1, 2]], jnp.int32)
    lengths = jnp.array([0, 12], jnp.int32)

    for out in (
        ref.paged_attention_ref(q, kp, vp, tables, lengths, page_size=page),
        ref.fused_paged_attention_ref(q, kv, tables, lengths,
                                      page_size=page),
        paged_attention_pallas(q, kp, vp, tables, lengths, page_size=page,
                               interpret=True),
        *[fused_paged_attention_pallas(q, kv, tables, lengths,
                                       page_size=page, num_buffers=nb,
                                       interpret=True) for nb in (1, 2, 4)],
    ):
        out = np.asarray(out)
        assert np.all(out[0] == 0.0), "padding row leaked page-0 garbage"
        assert np.all(np.isfinite(out)) and abs(out[1]).max() > 0

    H, r, rd = 4, 16, 8
    ql = jax.random.normal(ks[1], (B, H, r))
    qr = jax.random.normal(ks[2], (B, H, rd))
    ckv = jnp.full((P, page, r), 1e4)
    kr = jnp.full((P, page, rd), 1e4)
    mkv = fuse_mla(ckv, kr)
    for out in (
        ref.mla_paged_attention_ref(ql, qr, ckv, kr, tables, lengths,
                                    page_size=page, scale=0.2),
        ref.mla_fused_paged_attention_ref(ql, qr, mkv, tables, lengths,
                                          page_size=page, scale=0.2),
        mla_paged_attention_pallas(ql, qr, ckv, kr, tables, lengths,
                                   page_size=page, scale=0.2,
                                   interpret=True),
        *[mla_fused_paged_attention_pallas(ql, qr, mkv, tables, lengths,
                                           page_size=page, scale=0.2,
                                           num_buffers=nb, interpret=True)
          for nb in (1, 2, 4)],
    ):
        out = np.asarray(out)
        assert np.all(out[0] == 0.0), "padding row leaked page-0 garbage"
        assert np.all(np.isfinite(out)) and abs(out[1]).max() > 0


@pytest.mark.parametrize("window", [5, 12])
def test_windowed_radix_shared_prefix_parity(window):
    """Satellite audit: `window > 0` masking composed with radix-style
    block tables whose LEADING pages are shared across rows (the
    cross-request prefix-cache case) and a padded (length-0) row.
    Positions stay consecutive per path regardless of page sharing, so
    the windowed kernels must match a per-row dense gather exactly — no
    double-counting across the shared/private page boundary."""
    Hq, Hkv, D, P, page = 4, 2, 16, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(27), 3)
    kp = jax.random.normal(ks[1], (P, page, Hkv, D))
    vp = jax.random.normal(ks[2], (P, page, Hkv, D))
    kv = interleave_kv(kp, vp)
    # rows 0/1 share leading pages [2, 3] (radix-matched prefix), then
    # diverge into private pages; row 2 is a padding row
    tables = jnp.array([[2, 3, 5, -1],
                        [2, 3, 9, 11],
                        [-1, -1, -1, -1]], jnp.int32)
    lengths = jnp.array([19, 27, 0], jnp.int32)
    B = tables.shape[0]
    q = jax.random.normal(ks[0], (B, Hq, D))

    # independent oracle: per-row dense gather of the row's own pages,
    # then dense decode attention with the same window
    S = tables.shape[1] * page
    k_dense = kp[jnp.maximum(tables, 0)].reshape(B, S, Hkv, D)
    v_dense = vp[jnp.maximum(tables, 0)].reshape(B, S, Hkv, D)
    want = np.array(ref.decode_attention_ref(q, k_dense, v_dense, lengths,
                                             window=window))
    want[np.asarray(lengths) == 0] = 0.0

    legacy = paged_attention_pallas(q, kp, vp, tables, lengths,
                                    page_size=page, window=window,
                                    interpret=True)
    assert_allclose(np.asarray(legacy), want, rtol=1e-5, atol=1e-5)
    for nb in (1, 2, 4):
        got = fused_paged_attention_pallas(q, kv, tables, lengths,
                                           page_size=page, window=window,
                                           num_buffers=nb, interpret=True)
        assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_fused_dispatch_interpret(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 routes kops.fused_paged_attention /
    kops.mla_fused_paged_attention through the interpreted pipelined
    kernels; parity with the forced-reference path."""
    from repro.kernels import ops as kops

    B, hq, hkv, D, P, page = 2, 4, 2, 16, 8, 8
    q, _, _, kv = _fused_inputs(jax.random.PRNGKey(28), B, hq, hkv, D,
                                P, page)
    tables = jnp.array([[0, 1, -1], [2, 3, 4]], jnp.int32)
    lengths = jnp.array([11, 22], jnp.int32)
    kw = dict(page_size=page, window=6)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    got = kops.fused_paged_attention(q, kv, tables, lengths, **kw)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    want = kops.fused_paged_attention(q, kv, tables, lengths, **kw)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    H, r, rd = 4, 16, 8
    ql, qr, ckv, kr = _mla_inputs(jax.random.PRNGKey(29), B, H, r, rd,
                                  P, page, 3)
    mkv = fuse_mla(ckv, kr)
    mkw = dict(page_size=page, scale=0.2)
    monkeypatch.setenv("REPRO_FORCE_REF", "0")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    got = kops.mla_fused_paged_attention(ql, qr, mkv, tables, lengths,
                                         **mkw)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    want = kops.mla_fused_paged_attention(ql, qr, mkv, tables, lengths,
                                          **mkw)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# MLA (absorbed-latent) paged attention
# ---------------------------------------------------------------------------

def _mla_inputs(key, B, H, r, rd, P, page, MP):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (B, H, r)),
            jax.random.normal(ks[1], (B, H, rd)),
            jax.random.normal(ks[2], (P, page, r)),
            jax.random.normal(ks[3], (P, page, rd)))


@pytest.mark.parametrize("H,r,rd", [(4, 16, 8), (2, 32, 16), (8, 64, 32),
                                    (1, 16, 8)])
def test_mla_paged_attention(H, r, rd):
    """Parity vs the jnp oracle across head counts, ragged lengths, and
    padded (-1) block-table entries."""
    B, P, page, MP = 3, 24, 8, 5
    ql, qr, ckv, kr = _mla_inputs(jax.random.PRNGKey(8), B, H, r, rd,
                                  P, page, MP)
    tables = jnp.array([[3, 5, 1, -1, -1],
                        [0, 2, 7, 9, -1],
                        [11, 12, 13, 14, 15]], jnp.int32)
    lengths = jnp.array([19, 26, 40], jnp.int32)
    scale = 1.0 / ((r + rd) ** 0.5)
    got = mla_paged_attention_pallas(ql, qr, ckv, kr, tables, lengths,
                                     page_size=page, scale=scale,
                                     interpret=True)
    want = ref.mla_paged_attention_ref(ql, qr, ckv, kr, tables, lengths,
                                       page_size=page, scale=scale)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("lengths", [[1, 8, 9], [7, 16, 31], [32, 32, 32]])
def test_mla_paged_attention_lengths(lengths):
    """Sweep page-boundary lengths: single token, exact page multiples,
    one-past-page."""
    B, H, r, rd, P, page = 3, 4, 16, 8, 16, 8
    ql, qr, ckv, kr = _mla_inputs(jax.random.PRNGKey(9), B, H, r, rd,
                                  P, page, 4)
    tables = jnp.array([[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]],
                       jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    got = mla_paged_attention_pallas(ql, qr, ckv, kr, tables, ln,
                                     page_size=page, scale=0.25,
                                     interpret=True)
    want = ref.mla_paged_attention_ref(ql, qr, ckv, kr, tables, ln,
                                       page_size=page, scale=0.25)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


def test_mla_paged_dispatch_interpret(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 routes kops.mla_paged_attention through the
    interpreted Pallas kernel; parity with the reference path."""
    from repro.kernels import ops as kops

    B, H, r, rd, P, page = 2, 4, 16, 8, 8, 8
    ql, qr, ckv, kr = _mla_inputs(jax.random.PRNGKey(10), B, H, r, rd,
                                  P, page, 3)
    tables = jnp.array([[0, 1, -1], [2, 3, 4]], jnp.int32)
    lengths = jnp.array([11, 22], jnp.int32)
    kw = dict(page_size=page, scale=0.2)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    got = kops.mla_paged_attention(ql, qr, ckv, kr, tables, lengths, **kw)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    want = kops.mla_paged_attention(ql, qr, ckv, kr, tables, lengths, **kw)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,H,D", [(1, 5, 1, 8), (2, 16, 3, 16),
                                     (1, 33, 2, 64)])
def test_wkv6(B, T, H, D):
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, D)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, D)) + 2.0)
    u = jax.random.normal(ks[4], (H, D))
    s0 = jax.random.normal(ks[5], (B, H, D, D))
    got_o, got_s = wkv6_pallas(r, k, v, w, u, s0, interpret=True)
    want_o, want_s = ref.wkv6_ref(r, k, v, w, u, s0)
    assert_allclose(np.asarray(got_o), np.asarray(want_o),
                    rtol=5e-4, atol=5e-4)
    assert_allclose(np.asarray(got_s), np.asarray(want_s),
                    rtol=5e-4, atol=5e-4)


def _segment_layout(B, T, seed=0):
    """Ragged per-row segment labels with a tail pad, plus the column
    span of one interior segment per row (for leak checks)."""
    seg = np.full((B, T), -1, np.int32)
    spans = []
    cuts = [0, T // 3, 2 * T // 3, T - 2]
    for b in range(B):
        for s in range(len(cuts) - 1):
            seg[b, cuts[s]: cuts[s + 1]] = s
        spans.append((cuts[1], cuts[2]))
    return jnp.asarray(seg), spans


@pytest.mark.parametrize("B,T,d_in,N", [(2, 24, 8, 4), (1, 33, 16, 8)])
def test_mamba_scan_segment_reset(B, T, d_in, N):
    """Segment-reset parity (Pallas vs ref), per-segment equivalence to a
    fresh scan, and the leak case: without the reset, state from the
    previous segment would contaminate the next one."""
    ks = jax.random.split(jax.random.PRNGKey(12), 5)
    u = jax.random.normal(ks[0], (B, T, d_in))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, d_in)))
    B_ = jax.random.normal(ks[2], (B, T, N))
    C_ = jax.random.normal(ks[3], (B, T, N))
    A = -jnp.exp(jax.random.normal(ks[4], (d_in, N)) * 0.3)
    D = jnp.ones((d_in,))
    h0 = jnp.zeros((B, d_in, N))
    seg, spans = _segment_layout(B, T)
    got_y, got_h = mamba_scan_pallas(u, dt, B_, C_, A, D, h0, seg,
                                     blk_d=d_in, interpret=True)
    want_y, want_h = ref.mamba_scan_ref(u, dt, B_, C_, A, D, h0,
                                        segment_ids=seg)
    assert_allclose(np.asarray(got_y), np.asarray(want_y),
                    rtol=2e-5, atol=2e-5)
    assert_allclose(np.asarray(got_h), np.asarray(want_h),
                    rtol=2e-5, atol=2e-5)
    lo, hi = spans[0]
    # the interior segment scans exactly as it would in its own row
    solo_y, _ = ref.mamba_scan_ref(u[:, lo:hi], dt[:, lo:hi], B_[:, lo:hi],
                                   C_[:, lo:hi], A, D, h0)
    assert_allclose(np.asarray(want_y[:, lo:hi]), np.asarray(solo_y),
                    rtol=1e-5, atol=1e-5)
    # leak case: dropping the reset changes that segment's output
    leak_y, _ = ref.mamba_scan_ref(u, dt, B_, C_, A, D, h0)
    assert not np.allclose(np.asarray(leak_y[:, lo:hi]),
                           np.asarray(solo_y))


@pytest.mark.parametrize("B,T,H,D", [(2, 24, 2, 8), (1, 33, 1, 16)])
def test_wkv6_segment_reset(B, T, H, D):
    """Segment-reset parity (Pallas vs ref), per-segment equivalence to a
    fresh recurrence, and the leak case without the reset."""
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, D)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, D)) + 2.0)
    u = jax.random.normal(ks[4], (H, D))
    s0 = jnp.zeros((B, H, D, D))
    seg, spans = _segment_layout(B, T)
    got_o, got_s = wkv6_pallas(r, k, v, w, u, s0, seg, interpret=True)
    want_o, want_s = ref.wkv6_ref(r, k, v, w, u, s0, segment_ids=seg)
    assert_allclose(np.asarray(got_o), np.asarray(want_o),
                    rtol=5e-4, atol=5e-4)
    assert_allclose(np.asarray(got_s), np.asarray(want_s),
                    rtol=5e-4, atol=5e-4)
    lo, hi = spans[0]
    solo_o, _ = ref.wkv6_ref(r[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                             w[:, lo:hi], u, s0)
    assert_allclose(np.asarray(want_o[:, lo:hi]), np.asarray(solo_o),
                    rtol=1e-5, atol=1e-5)
    leak_o, _ = ref.wkv6_ref(r, k, v, w, u, s0)
    assert not np.allclose(np.asarray(leak_o[:, lo:hi]),
                           np.asarray(solo_o))


def test_recurrent_segment_dispatch_interpret(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 routes the segment-reset kops through the
    interpreted kernels; parity with the forced-reference path."""
    from repro.kernels import ops as kops

    B, T, H, D = 1, 12, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(14), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, D)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, D)))
    u = jax.random.normal(ks[4], (H, D))
    s0 = jnp.zeros((B, H, D, D))
    seg = jnp.asarray(np.repeat([[0, 1, 2]], 4, axis=1).reshape(1, 12))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    got_o, got_s = kops.wkv6(r, k, v, w, u, s0, segment_ids=seg)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    want_o, want_s = kops.wkv6(r, k, v, w, u, s0, segment_ids=seg)
    assert_allclose(np.asarray(got_o), np.asarray(want_o),
                    rtol=5e-4, atol=5e-4)
    assert_allclose(np.asarray(got_s), np.asarray(want_s),
                    rtol=5e-4, atol=5e-4)


def test_wkv6_state_chaining():
    """Running two halves with carried state == one full run."""
    B, T, H, D = 1, 12, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, D)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, D)))
    u = jax.random.normal(ks[4], (H, D))
    s0 = jnp.zeros((B, H, D, D))
    o_full, s_full = ref.wkv6_ref(r, k, v, w, u, s0)
    h = T // 2
    o1, s1 = wkv6_pallas(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u, s0,
                         interpret=True)
    o2, s2 = wkv6_pallas(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u, s1,
                         interpret=True)
    assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                    np.asarray(o_full), rtol=5e-4, atol=5e-4)
    assert_allclose(np.asarray(s2), np.asarray(s_full), rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# ops dispatch: gradients through kernels, CPU-only switches
# ---------------------------------------------------------------------------

def _grad_cases():
    ks = jax.random.split(jax.random.PRNGKey(21), 8)
    seg = jnp.asarray(np.repeat([[0] * 10 + [1] * 14], 2, 0), jnp.int32)
    q = jax.random.normal(ks[0], (2, 24, 4, 32))
    kv = [jax.random.normal(ks[i], (2, 24, 2, 32)) for i in (1, 2)]
    B, T, d_in, N, H, D = 1, 8, 16, 4, 2, 8
    mamba = (jax.random.normal(ks[3], (B, T, d_in)),
             jax.nn.softplus(jax.random.normal(ks[4], (B, T, d_in))),
             jax.random.normal(ks[5], (B, T, N)),
             jax.random.normal(ks[6], (B, T, N)),
             -jnp.exp(jax.random.normal(ks[7], (d_in, N)) * 0.3),
             jnp.ones((d_in,)), jnp.zeros((B, d_in, N)))
    rkv = [jax.random.normal(ks[i], (B, T, H, D)) for i in range(3)]
    wkv = (*rkv, jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, D))),
           jax.random.normal(ks[4], (H, D)), jnp.zeros((B, H, D, D)))
    return {
        "rmsnorm": (lambda x, s: ops.rmsnorm(x, s),
                    lambda x, s: ref.rmsnorm_ref(x, s),
                    (jax.random.normal(ks[0], (5, 64)),
                     jax.random.normal(ks[1], (64,)))),
        "flash_segments": (
            lambda q, k, v: ops.flash_attention(q, k, v, segment_ids=seg),
            lambda q, k, v: ref.attention_ref(q, k, v, segment_ids=seg),
            (q, *kv)),
        "mamba_scan": (lambda *a: ops.mamba_scan(*a)[0],
                       lambda *a: ref.mamba_scan_ref(*a)[0], mamba),
        "wkv6": (lambda *a: ops.wkv6(*a)[0],
                 lambda *a: ref.wkv6_ref(*a)[0], wkv),
    }


@pytest.mark.parametrize("op", ["rmsnorm", "flash_segments", "mamba_scan",
                                "wkv6"])
def test_ops_gradient_through_kernel(monkeypatch, op):
    """The PPO update differentiates through ``ops``; ``pallas_call`` has
    no transpose rule, so kernel ops take the reference's VJP — the
    gradient must equal the reference's own, every differentiable input."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    fn, want_fn, args = _grad_cases()[op]
    argnums = tuple(range(len(args)))
    got = jax.grad(lambda *a: (fn(*a) ** 2).sum(), argnums)(*args)
    want = jax.grad(lambda *a: (want_fn(*a) ** 2).sum(), argnums)(*args)
    for g, w in zip(got, want):
        assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("switch", ["REPRO_PALLAS_INTERPRET",
                                    "REPRO_FORCE_REF"])
def test_cpu_switches_raise_on_tpu(monkeypatch, switch):
    """On a TPU backend the interpreter / reference switches must fail
    loudly, never quietly replace the compiled kernel; on the CPU they
    keep their meaning."""
    x, s = jnp.ones((8, 128)), jnp.ones((128,))
    monkeypatch.setenv(switch, "1")
    want = ref.rmsnorm_ref(x, s)
    assert_allclose(np.asarray(ops.rmsnorm(x, s)), np.asarray(want),
                    rtol=1e-6, atol=1e-6)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match=switch):
        ops.rmsnorm(x, s)
