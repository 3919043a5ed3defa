"""Pallas kernel validation (Pallas interpreter on CPU): shape/dtype sweeps +
property tests against the pure-jnp oracles in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # property tests report as skipped; rest run
    st = None

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.lowering_conv import ops as lc_ops
from repro.kernels.lowering_conv import ref as lc_ref
from repro.kernels.lowering_conv import vmem_bytes


# ---------------------------------------------------------------------------
# lowering_conv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,kern,stride", [
    ((4, 12, 12, 3), (3, 3, 3, 8), 1),
    ((2, 16, 16, 4), (5, 5, 4, 8), 1),
    ((4, 13, 13, 2), (3, 3, 2, 16), 2),
    ((1, 28, 28, 1), (5, 5, 1, 20), 1),     # LeNet conv1
    ((2, 31, 31, 3), (11, 11, 3, 16), 4),   # CaffeNet conv1 geometry
])
def test_lowering_conv_sweep(shape, kern, stride, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), kern).astype(dtype)
    ref = lc_ref.conv_ref(x.astype(jnp.float32), w.astype(jnp.float32), stride)
    out = lc_ops.lowering_conv(x, w, stride=stride, bp=2, rb=4)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bp,rb", [(1, 1), (1, 4), (2, 2), (4, 8), (8, 8)])
def test_lowering_conv_block_sizes(bp, rb):
    """The paper's b_p sweep (Fig. 4): every block size computes the same
    function; only the footprint/efficiency changes."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 10, 10, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 8))
    ref = lc_ref.conv_ref(x, w, 1)
    out = lc_ops.lowering_conv(x, w, stride=1, bp=bp, rb=rb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_lowering_matches_three_phase_ref():
    """Kernel implements the paper's lowering/GEMM/lifting algorithm."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 9, 9, 2))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 2, 4))
    a = lc_ref.lowered_conv_ref(x, w, 1)
    b = lc_ops.lowering_conv(x, w, stride=1)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_vmem_model_linear_in_bp():
    """Fig. 4(c): footprint grows linearly with b_p."""
    kw = dict(rb=4, h=16, w=16, cin=8, kh=3, kw=3, cout=32)
    m1 = vmem_bytes(bp=1, **kw)
    m2 = vmem_bytes(bp=2, **kw)
    m4 = vmem_bytes(bp=4, **kw)
    assert abs((m4 - m2) - 2 * (m2 - m1)) < 1e-6 * m4


if st is None:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_lowering_conv_property():
        pass
else:
    @settings(max_examples=10, deadline=None)
    @given(b=st.integers(1, 4), hw=st.sampled_from([8, 11, 14]),
           k=st.sampled_from([1, 3]), cin=st.integers(1, 4),
           cout=st.sampled_from([4, 8]), seed=st.integers(0, 2**30))
    def test_lowering_conv_property(b, hw, k, cin, cout, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        x = jax.random.normal(ks[0], (b, hw, hw, cin))
        w = jax.random.normal(ks[1], (k, k, cin, cout))
        ref = lc_ref.conv_ref(x, w, 1)
        out = lc_ops.lowering_conv(x, w, stride=1, bp=2, rb=3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,kv,hd,window", [
    (64, 4, 4, 16, None),
    (64, 4, 2, 16, None),      # GQA
    (128, 2, 1, 32, None),     # MQA
    (64, 4, 2, 16, 16),        # sliding window
    (96, 2, 2, 64, 32),
])
def test_flash_attention_sweep(s, h, kv, hd, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, s, h, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (2, s, kv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (2, s, kv, hd)).astype(dtype)
    rep = h // kv
    ref = fa_ref.attention_ref(
        jnp.repeat(q, 1, 2).astype(jnp.float32),
        jnp.repeat(k, rep, 2).astype(jnp.float32),
        jnp.repeat(v, rep, 2).astype(jnp.float32),
        causal=True, window=window)
    out = fa_ops.flash_attention(q, k, v, causal=True, window=window,
                                 bq=32, bk=32)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bq,bk", [(8, 8), (16, 64), (64, 16), (128, 128)])
def test_flash_attention_block_sizes(bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 16))
    k = jax.random.normal(ks[1], (1, 128, 2, 16))
    v = jax.random.normal(ks[2], (1, 128, 2, 16))
    ref = fa_ref.attention_ref(q, k, v, causal=True)
    out = fa_ops.flash_attention(q, k, v, bq=bq, bk=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


if st is None:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_flash_attention_property():
        pass
else:
    @settings(max_examples=8, deadline=None)
    @given(s=st.sampled_from([32, 48, 64]), h=st.sampled_from([1, 2]),
           window=st.sampled_from([None, 8, 16]), seed=st.integers(0, 2**30))
    def test_flash_attention_property(s, h, window, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (1, s, h, 8))
        k = jax.random.normal(ks[1], (1, s, h, 8))
        v = jax.random.normal(ks[2], (1, s, h, 8))
        ref = fa_ref.attention_ref(q, k, v, causal=True, window=window)
        out = fa_ops.flash_attention(q, k, v, window=window, bq=16, bk=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("h,kv", [(4, 2), (4, 1), (2, 2)])
def test_flash_attention_gqa_no_repeat_bitwise(h, kv):
    """GQA without materializing ``jnp.repeat``: the kernel's
    query-head -> kv-head index mapping must be BITWISE equal to feeding
    it explicitly repeated K/V (same blocks, same reduction order — the
    wrapper only changed which rows the BlockSpec index maps fetch)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 64, h, 16))
    k = jax.random.normal(ks[1], (2, 64, kv, 16))
    v = jax.random.normal(ks[2], (2, 64, kv, 16))
    grouped = fa_ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    rep = h // kv
    repeated = fa_ops.flash_attention(q, jnp.repeat(k, rep, axis=2),
                                      jnp.repeat(v, rep, axis=2),
                                      causal=True, bq=32, bk=32)
    assert np.array_equal(np.asarray(grouped), np.asarray(repeated))


def test_flash_attention_rejects_indivisible_heads():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 32, 3, 8))
    k = jax.random.normal(ks[1], (1, 32, 2, 8))
    v = jax.random.normal(ks[2], (1, 32, 2, 8))
    with pytest.raises(ValueError):
        fa_ops.flash_attention(q, k, v)


def test_flash_matches_model_attention_path():
    """models.layers.attention_forward(attn_impl='pallas') path parity."""
    from repro.configs.base import ArchConfig
    from repro.models import layers as L
    cfg = ArchConfig(name="t", arch_type="dense", num_layers=1, d_model=64,
                     num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
                     vocab_size=64, compute_dtype="float32", remat=False)
    p = L.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    y_ref, _ = L.attention_forward(p, x, cfg, attn_impl="xla")
    y_pal, _ = L.attention_forward(p, x, cfg, attn_impl="pallas")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
