"""Trainable lowering conv: custom-VJP gradients vs jax.grad through the
XLA reference conv, the backward tiling/footprint model, and the tile
autotuner (docs/lowering_conv.md)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # property tests report as skipped; rest run
    st = None

from repro.engine import timing
from repro.kernels.lowering_conv import (autotune, bwd, choose_tiles,
                                         ops as lc_ops, vmem_bytes)
from repro.kernels.lowering_conv.ref import conv_ref, lower
from repro.models import cnn as C


def _rel_err(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def _layer_cases():
    """Every conv layer geometry of the three archs' smoke configs (which
    preserve the families' strides/pools — caffenet-smoke keeps the
    strided big-kernel conv1), plus the real CaffeNet conv1 kernel
    (11x11 stride 4) on a reduced image."""
    cases = []
    for arch in ("lenet", "cifarnet", "caffenet"):
        cfg = C.get_cnn_smoke_config(arch)
        for x_shape, w_shape, stride in C.conv_layer_shapes(cfg, 4):
            cases.append(pytest.param(x_shape, w_shape, stride,
                                      id=f"{arch}-{w_shape[0]}x{w_shape[1]}"
                                         f"s{stride}c{w_shape[3]}"))
    cases.append(pytest.param((2, 31, 31, 3), (11, 11, 3, 16), 4,
                              id="caffenet-conv1-11x11s4"))
    return cases


@pytest.mark.parametrize("x_shape,w_shape,stride", _layer_cases())
def test_custom_vjp_matches_xla_autodiff_per_layer(x_shape, w_shape, stride):
    """Acceptance: custom-VJP gradients match jax.grad through the XLA
    reference conv to <= 1e-5 relative error for all three archs' layer
    shapes (stride > 1 included)."""
    x = jax.random.normal(jax.random.PRNGKey(0), x_shape)
    w = jax.random.normal(jax.random.PRNGKey(1), w_shape) * 0.1

    def loss(conv):
        # non-linear readout so dy is not constant
        return lambda x, w: (jax.nn.relu(conv(x, w)) ** 2).sum()

    ref = jax.grad(loss(lambda x, w: conv_ref(x, w, stride)), (0, 1))(x, w)
    got_xla = jax.grad(loss(
        lambda x, w: lc_ops.lowering_conv_xla(x, w, stride=stride)),
        (0, 1))(x, w)
    got_pal = jax.grad(loss(
        lambda x, w: lc_ops.lowering_conv(x, w, stride=stride, bp=2, rb=3)), (0, 1))(x, w)
    for got, name in ((got_xla, "xla"), (got_pal, "pallas")):
        assert _rel_err(got[0], ref[0]) <= 1e-5, (name, "dx")
        assert _rel_err(got[1], ref[1]) <= 1e-5, (name, "dw")


@pytest.mark.parametrize("arch", ["lenet", "cifarnet", "caffenet"])
@pytest.mark.parametrize("impl", ["lowering", "lowering_interpret",
                                  "lowering_autodiff"])
def test_full_model_grads_match_xla(arch, impl):
    """End-to-end: the smoke CNN loss (pooled layers included) gives the
    same parameter gradients under every lowering impl as under the
    native-conv path."""
    cfg = dataclasses.replace(C.get_cnn_smoke_config(arch), conv_impl=impl)
    cfg_ref = dataclasses.replace(cfg, conv_impl="xla")
    params = C.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"images": jax.random.normal(
                 jax.random.PRNGKey(2),
                 (8, cfg.image_size, cfg.image_size, cfg.in_channels)),
             "labels": jax.random.randint(jax.random.PRNGKey(3), (8,), 0,
                                          cfg.num_classes)}
    g = jax.grad(lambda p: C.loss_fn(p, batch, cfg))(params)
    g_ref = jax.grad(lambda p: C.loss_fn(p, batch, cfg_ref))(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        assert _rel_err(a, b) <= 1e-5


def test_needs_dgrad_false_skips_input_gradient():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 12, 12, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 8)) * 0.1

    def run(conv):
        return jax.grad(lambda x, w: (conv(x, w) ** 2).sum(), (0, 1))(x, w)

    full = run(lambda x, w: lc_ops.lowering_conv_xla(x, w, stride=1))
    skip = run(lambda x, w: lc_ops.lowering_conv_xla(x, w, stride=1,
                                                     needs_dgrad=False))
    assert float(jnp.abs(skip[0]).max()) == 0.0      # dx suppressed
    np.testing.assert_allclose(np.asarray(skip[1]), np.asarray(full[1]),
                               rtol=1e-6, atol=1e-6)  # dw untouched


def test_grouped_vmap_custom_vjp_matches():
    """The engine's group-vmap path batches the custom VJP (traced forms):
    gradients must survive vmap."""
    cfg = C.get_cnn_smoke_config("caffenet")     # conv_impl="lowering"
    params = C.init_params(jax.random.PRNGKey(0), cfg)
    imgs = jax.random.normal(
        jax.random.PRNGKey(2),
        (2, 4, cfg.image_size, cfg.image_size, cfg.in_channels))
    labels = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0,
                                cfg.num_classes)

    def loss(p, b):
        return C.loss_fn(p, b, cfg)

    vg = jax.vmap(jax.grad(loss), in_axes=(None, 0))(
        params, {"images": imgs, "labels": labels})
    for g in range(2):
        ref = jax.grad(loss)(params,
                             {"images": imgs[g], "labels": labels[g]})
        for a, b in zip(jax.tree.leaves(vg), jax.tree.leaves(ref)):
            assert _rel_err(a[g], b) <= 1e-5


# ---------------------------------------------------------------------------
# tiling model
# ---------------------------------------------------------------------------

def _fwd_blockspec_elems(bp, rb, h, w, cin, kh, kw, cout, stride):
    """Element counts of the refs `lowering_conv_pallas` actually binds:
    its in_specs (image block, kernel matrix), out_spec, and the lowered
    tile it builds in-kernel. Written out independently here so a change
    to either the kernel's BlockSpecs or the vmem model without the other
    fails this test."""
    wo = (w - kw) // stride + 1
    K = kh * kw * cin
    return (bp * h * w * cin) + (K * cout) + (bp * rb * wo * cout) \
        + (bp * rb * wo * K)


def _wgrad_blockspec_elems(bp, rb, h, w, cin, kh, kw, cout, stride):
    wo = (w - kw) // stride + 1
    K = kh * kw * cin
    return (bp * rb * wo * K) + (bp * rb * wo * cout) + (K * cout)


def _dgrad_blockspec_elems(bp, h, w, cin, kh, kw, cout, stride):
    ho = (h - kh) // stride + 1
    wo = (w - kw) // stride + 1
    K = kh * kw * cin
    return (bp * ho * wo * cout) + (K * cout) + (bp * ho * wo * K) \
        + (bp * h * w * cin)


if st is None:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_vmem_model_matches_blockspecs():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_choose_tiles_returns_divisors():
        pass
else:
    @settings(max_examples=30, deadline=None)
    @given(bp=st.integers(1, 8), rb=st.integers(1, 8),
           hw=st.sampled_from([12, 16, 21, 33]), cin=st.integers(1, 4),
           k=st.sampled_from([3, 5, 7]), cout=st.sampled_from([4, 16]),
           stride=st.sampled_from([1, 2, 4]))
    def test_vmem_model_matches_blockspecs(bp, rb, hw, cin, k, cout, stride):
        if hw <= k:
            return
        geom = dict(h=hw, w=hw, cin=cin, kh=k, kw=k, cout=cout,
                    stride=stride)
        assert vmem_bytes(bp=bp, rb=rb, pass_="fwd", **geom) == \
            4 * _fwd_blockspec_elems(bp, rb, stride=stride, cin=cin, kh=k,
                                     kw=k, cout=cout, h=hw, w=hw)
        assert vmem_bytes(bp=bp, rb=rb, pass_="wgrad", **geom) == \
            4 * _wgrad_blockspec_elems(bp, rb, stride=stride, cin=cin,
                                       kh=k, kw=k, cout=cout, h=hw, w=hw)
        assert vmem_bytes(bp=bp, rb=rb, pass_="dgrad", **geom) == \
            4 * _dgrad_blockspec_elems(bp, stride=stride, cin=cin, kh=k,
                                       kw=k, cout=cout, h=hw, w=hw)

    @settings(max_examples=40, deadline=None)
    @given(b=st.integers(1, 64), ho=st.integers(1, 64),
           bp=st.integers(1, 64), rb=st.integers(1, 64))
    def test_choose_tiles_returns_divisors(b, ho, bp, rb):
        """Forward and backward kernels resolve requested tiles through
        choose_tiles: results must divide the batch / output rows and
        never exceed the request (so grids are exact, no remainder
        handling in-kernel)."""
        bp_c, rb_c = choose_tiles(b, ho, bp, rb)
        assert b % bp_c == 0 and ho % rb_c == 0
        assert 1 <= bp_c <= max(1, min(bp, b))
        assert 1 <= rb_c <= max(1, min(rb, ho))


def test_vmem_model_unknown_pass_rejected():
    with pytest.raises(ValueError, match="unknown pass_"):
        vmem_bytes(bp=1, rb=1, h=8, w=8, cin=1, kh=3, kw=3, cout=4,
                   pass_="bogus")


def test_bwd_kernels_match_xla_forms():
    """Pallas wgrad/dgrad (interpret) == the XLA reference forms."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 13, 13, 2))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 2, 8)) * 0.1
    stride = 2
    ho = (13 - 3) // stride + 1
    dy = jax.random.normal(jax.random.PRNGKey(2), (4, ho, ho, 8))
    d_hat = lower(x, 3, 3, stride)
    dw_ref = bwd.wgrad_xla(d_hat, dy, w.shape)
    lowered = d_hat.reshape(4, ho, ho, -1)
    dw_pal = bwd.wgrad_pallas(lowered, dy, w.shape, bp=2, rb=2)
    np.testing.assert_allclose(np.asarray(dw_pal), np.asarray(dw_ref),
                               rtol=2e-5, atol=2e-5)
    dx_ref = bwd.dgrad_xla(dy, w, x.shape, stride)
    dx_pal = bwd.dgrad_pallas(dy, w, x.shape, stride=stride, bp=2)
    np.testing.assert_allclose(np.asarray(dx_pal), np.asarray(dx_ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def test_tile_candidates_divisors_under_budget():
    x_shape, w_shape = (8, 16, 16, 3), (3, 3, 3, 8)
    budget = 256 << 10
    cands = autotune.tile_candidates(x_shape, w_shape, 1,
                                     budget_bytes=budget)
    assert cands, "at least one candidate"
    ho = 14
    geom = dict(h=16, w=16, cin=3, kh=3, kw=3, cout=8, stride=1)
    for bp, rb in cands:
        assert 8 % bp == 0 and ho % rb == 0
        for p in ("fwd", "wgrad", "dgrad"):
            assert vmem_bytes(bp=bp, rb=rb, pass_=p, **geom) <= budget


def test_autotune_caches_per_shape_and_stride(monkeypatch):
    autotune.clear_tile_cache()
    x_shape, w_shape = (4, 12, 12, 2), (3, 3, 2, 4)
    t1 = autotune.autotune_tiles(x_shape, w_shape, 1, iters=1, warmup=1)
    assert 4 % t1[0] == 0 and 10 % t1[1] == 0
    assert autotune.cached_tiles(x_shape, w_shape, 1) == t1
    # a second call must hit the cache — probing again would retime
    monkeypatch.setattr(
        autotune.timing, "probe",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("re-probed")))
    assert autotune.autotune_tiles(x_shape, w_shape, 1) == t1
    # the key ignores the batch dim: the engine traces the same layer at
    # batch/g (group vmap) or batch/(g*k) (per-device shards) and must
    # still hit the probed choice
    assert autotune.cached_tiles((1,) + x_shape[1:], w_shape, 1) == t1
    assert autotune.cached_tiles((64,) + x_shape[1:], w_shape, 1) == t1
    # different stride (or geometry) is a different cache line -> default
    assert autotune.cached_tiles(x_shape, w_shape, 2) == \
        autotune.DEFAULT_TILES
    monkeypatch.undo()
    # a SMALLER budget the cached choice doesn't fit forces a re-probe
    # under the new budget (a TPU budget exists to prevent VMEM OOM)
    tiny = autotune._max_vmem(1, 1, x_shape, w_shape, 1)
    t2 = autotune.autotune_tiles(x_shape, w_shape, 1, budget_bytes=tiny,
                                 iters=1, warmup=1)
    assert autotune._max_vmem(*t2, x_shape, w_shape, 1) <= tiny
    autotune.clear_tile_cache()


# ---------------------------------------------------------------------------
# timing stats (the bench emitters' min+median+IQR contract)
# ---------------------------------------------------------------------------

def test_time_stats_min_median_iqr():
    s = timing.stats_of([5.0, 1.0, 3.0, 2.0, 4.0])
    assert s.min_s == 1.0 and s.median_s == 3.0
    assert s.iqr_s == pytest.approx(2.0)
    assert s.iters == 5
    row = s.row()
    assert set(row) == {"min_us", "median_us", "iqr_us", "iters"}
    assert row["min_us"] <= row["median_us"]


def test_probe_returns_stats():
    x = jnp.ones((16, 16))
    f = jax.jit(lambda: x @ x)
    s = timing.probe(f, warmup=1, iters=3)
    assert s.iters == 3 and s.min_s > 0 and s.min_s <= s.median_s
