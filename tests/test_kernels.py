"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles.

Sweeps shapes/dtypes per the deliverable; hypothesis drives randomized
shape/content generation for the attention and recurrence kernels.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is optional: the property-based cases fall back to a fixed
# sample sweep so tier-1 collection never depends on it
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _rand(rng, shape, dtype, scale=1.0):
    x = rng.standard_normal(shape) * scale
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 384),
                                   (128, 512, 128), (384, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes(rng, m, k, n, dtype):
    x = _rand(rng, (m, k), dtype)
    w = _rand(rng, (k, n), dtype)
    got = ops.matmul(x, w, impl="interpret")
    want = ref.matmul(x, w)
    # blocked K accumulation reorders fp adds -> small drift vs single dot
    tol = 2e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_matmul_block_sweep(rng):
    x = _rand(rng, (256, 256), jnp.float32)
    w = _rand(rng, (256, 256), jnp.float32)
    want = ref.matmul(x, w)
    for bm, bn, bk in [(64, 64, 64), (128, 256, 64), (256, 128, 128)]:
        got = ops.matmul(x, w, impl="interpret", block_m=bm, block_n=bn,
                         block_k=bk)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64),
                                           (True, 128)])
def test_flash_attention_gqa_masks(rng, heads, kv_heads, causal, window):
    sq = sk = 256
    d = 64
    q = _rand(rng, (heads, sq, d), jnp.float32)
    k = _rand(rng, (kv_heads, sk, d), jnp.float32)
    v = _rand(rng, (kv_heads, sk, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="interpret")
    want = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-4), (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(rng, dtype, tol):
    q = _rand(rng, (2, 128, 64), dtype)
    k = _rand(rng, (2, 128, 64), dtype)
    v = _rand(rng, (2, 128, 64), dtype)
    got = ops.flash_attention(q, k, v, impl="interpret")
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _flash_attention_case(nq, nk, window, seed):
    """Right-aligned chunked query attention equals the dense oracle for
    arbitrary (query chunk, key length, window) combinations."""
    rng = np.random.default_rng(seed)
    d = 32
    sq, sk = nq * 64, nk * 64
    if sq > sk:
        sq = sk
    q = jnp.asarray(rng.standard_normal((2, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, sk, d)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              impl="interpret", block_q=64, block_k=64)
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(nq=st.sampled_from([1, 2, 4]), nk=st.sampled_from([2, 4]),
           window=st.sampled_from([0, 32, 96]), seed=st.integers(0, 2**16))
    def test_flash_attention_property(nq, nk, window, seed):
        _flash_attention_case(nq, nk, window, seed)
else:
    @pytest.mark.parametrize("nq,nk,window", [(1, 2, 0), (2, 4, 32),
                                              (4, 2, 96), (4, 4, 0)])
    def test_flash_attention_property(nq, nk, window):
        _flash_attention_case(nq, nk, window, seed=0)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("blocks_per_wave", [2, 4])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("group", [1, 2])
def test_paged_decode_attention_matches_gather(rng, group, dtype, tol,
                                               blocks_per_wave, layer):
    """The kernel over live blocks equals the XLA gather + repeat path on
    every row it serves: lengths 1, bs - 1, bs, bs + 1 and the full table,
    through a shuffled, non-contiguous block table, with read-only shared
    blocks ``-(p + 2)``.  A fully unmapped row comes back finite and
    leaves the other rows alone.  The arenas are a stack of three layers
    with distinct contents, read at ``layer``: both reads equal the XLA
    read of that layer's arena alone (a stack of one)."""
    bs, m, hkv, d, n_layers = 4, 6, 2, 16, 3
    lengths = np.array([1, bs - 1, bs, bs + 1, m * bs, 2 * bs + 3, 5])
    n_blocks = -(-lengths // bs)
    p = int(n_blocks.sum()) + 3
    phys = iter(rng.permutation(p))
    table = np.full((len(lengths), m), -1, np.int32)
    for row, n in enumerate(n_blocks[:-1]):
        table[row, :n] = [next(phys) for _ in range(n)]
    table[5, :2] = -(table[5, :2] + 2)        # a shared read-only prefix
    table[1, 0] = -(table[1, 0] + 2)
    q = _rand(rng, (len(lengths), hkv * group, d), dtype)
    k_arena = _rand(rng, (n_layers, p, bs, hkv, d), dtype)
    v_arena = _rand(rng, (n_layers, p, bs, hkv, d), dtype)
    rows = (jnp.asarray(table), jnp.asarray(lengths))
    args = (q, k_arena, v_arena) + rows + (jnp.int32(layer),)
    got = np.asarray(ops.paged_decode_attention(
        *args, impl="interpret", blocks_per_wave=blocks_per_wave),
        np.float32)
    xla = np.asarray(ops.paged_decode_attention(*args, impl="xla"),
                     np.float32)
    one = slice(layer, layer + 1)
    want = np.asarray(ops.paged_decode_attention(
        q, k_arena[one], v_arena[one], *rows, jnp.int32(0), impl="xla"),
        np.float32)
    np.testing.assert_array_equal(xla, want)
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=tol, atol=tol)
    assert np.isfinite(got[-1]).all()


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,chunk", [(128, 32), (256, 128), (256, 256)])
@pytest.mark.parametrize("p,n", [(16, 32), (32, 16)])
def test_ssd_scan_shapes(rng, s, chunk, p, n):
    b, h = 2, 3
    x = _rand(rng, (b, s, h, p), jnp.float32, 0.5)
    dt = jax.nn.softplus(_rand(rng, (b, s, h), jnp.float32))
    a = -jnp.exp(_rand(rng, (h,), jnp.float32, 0.3))
    bb = _rand(rng, (b, s, n), jnp.float32, 0.3)
    cc = _rand(rng, (b, s, n), jnp.float32, 0.3)
    y1, h1 = ops.ssd_scan(x, dt, a, bb, cc, impl="interpret", chunk=chunk)
    y2, h2 = ref.ssd_scan(x, dt, a, bb, cc)
    np.testing.assert_allclose(y1, y2, rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(h1, h2, rtol=3e-3, atol=3e-3)


def test_ssd_model_chunked_matches_sequential(rng):
    """The model-level chunked SSD (repro.models.ssm) == sequential oracle."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 2, 128, 2, 8, 16
    x = _rand(rng, (b, s, h, p), jnp.float32, 0.5)
    dt = jax.nn.softplus(_rand(rng, (b, s, h), jnp.float32))
    a = -jnp.exp(_rand(rng, (h,), jnp.float32, 0.3))
    bb = _rand(rng, (b, s, n), jnp.float32, 0.3)
    cc = _rand(rng, (b, s, n), jnp.float32, 0.3)
    d_skip = jnp.zeros((h,), jnp.float32)
    y1, h1 = ssd_chunked(x, dt, a, bb, cc, d_skip, chunk=32)
    y2, h2 = ref.ssd_scan(x, dt, a, bb, cc)
    np.testing.assert_allclose(y1, y2, rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(h1, h2, rtol=3e-3, atol=3e-3)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------
def _rglru_case(s, l, chunk, seed):
    rng = np.random.default_rng(seed)
    a = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((2, s, l)), jnp.float32))
    b = jnp.asarray(rng.standard_normal((2, s, l)), jnp.float32) * 0.3
    h1, hf1 = ops.rglru_scan(a, b, impl="interpret", chunk=chunk, block_l=l)
    h2, hf2 = ref.rglru_scan(a, b)
    np.testing.assert_allclose(h1, h2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(hf1, hf2, rtol=2e-4, atol=2e-4)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(s=st.sampled_from([64, 128, 256]), l=st.sampled_from([32, 64]),
           chunk=st.sampled_from([32, 64]), seed=st.integers(0, 2**16))
    def test_rglru_property(s, l, chunk, seed):
        _rglru_case(s, l, chunk, seed)
else:
    @pytest.mark.parametrize("s,l,chunk", [(64, 32, 32), (128, 64, 32),
                                           (256, 32, 64)])
    def test_rglru_property(s, l, chunk):
        _rglru_case(s, l, chunk, seed=0)


# ---------------------------------------------------------------------------
# MoE grouped FFN
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("e,c,d,f,bc", [(4, 64, 32, 48, 32), (8, 128, 64, 32, 64),
                                        (2, 128, 128, 128, 128)])
def test_moe_ffn_shapes(rng, e, c, d, f, bc):
    buf = _rand(rng, (e, c, d), jnp.float32, 0.3)
    w1 = _rand(rng, (e, d, f), jnp.float32, 0.2)
    w3 = _rand(rng, (e, d, f), jnp.float32, 0.2)
    w2 = _rand(rng, (e, f, d), jnp.float32, 0.2)
    got = ops.moe_ffn(buf, w1, w3, w2, impl="interpret", block_c=bc)
    want = ref.moe_ffn(buf, w1, w3, w2)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_moe_ffn_bf16(rng):
    e, c, d, f = 2, 64, 32, 32
    buf = _rand(rng, (e, c, d), jnp.bfloat16, 0.3)
    w1 = _rand(rng, (e, d, f), jnp.bfloat16, 0.2)
    w3 = _rand(rng, (e, d, f), jnp.bfloat16, 0.2)
    w2 = _rand(rng, (e, f, d), jnp.bfloat16, 0.2)
    got = ops.moe_ffn(buf, w1, w3, w2, impl="interpret")
    want = ref.moe_ffn(buf, w1, w3, w2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)
