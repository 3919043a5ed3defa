"""Closed-form fused grouped update vs the sequential scan reference:
coefficient algebra, leaf-kernel parity (XLA ref + Pallas interpret), full
train-step equivalence, and the g=1 reduction to plain sgd_update."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.async_sgd import (make_grouped_train_step,
                                  scan_grouped_update)
from repro.core.workload import mlp_classify
from repro.kernels.fused_update.fused_update import fused_update_pallas
from repro.kernels.fused_update.ops import fused_group_update, fused_update
from repro.kernels.fused_update.ref import fused_update_ref
from repro.optim.closed_form import grouped_coeffs, head_coeffs
from repro.optim.sgd import sgd_update


def _tree(key, extra_leaves=True):
    ks = jax.random.split(key, 4)
    t = {"w": jax.random.normal(ks[0], (37, 53)),
         "fc": jax.random.normal(ks[1], (13,))}
    if extra_leaves:
        t["b"] = jax.random.normal(ks[2], (5, 3, 7))
        t["s"] = jnp.float32(0.3)          # scalar leaf
    return t


def _grads(key, params, g):
    return jax.tree.map(
        lambda p: jax.random.normal(key, (g,) + p.shape), params)


def _assert_trees_close(a, b, rtol=1e-5, atol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# update-application equivalence (no loss fn — direct on stacked gradients)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g", [1, 2, 4, 8])
@pytest.mark.parametrize("mu", [0.0, 0.9])
@pytest.mark.parametrize("wd", [0.0, 1e-4])
def test_fused_matches_scan(g, mu, wd):
    params = _tree(jax.random.PRNGKey(0))
    mom = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)
    grads = _grads(jax.random.PRNGKey(7), params, g)
    mask = jax.tree.map(lambda _: False, params)
    mask["fc"] = True                       # merged-FC head leaf
    ref_p, ref_v = scan_grouped_update(params, grads, mom, lr=0.05,
                                       momentum=mu, weight_decay=wd,
                                       head_mask=mask)
    c = grouped_coeffs(g, lr=0.05, momentum=mu, weight_decay=wd)
    hc = head_coeffs(g, lr=0.05, momentum=mu, weight_decay=wd)
    for impl in ("xla", "pallas"):
        p, v = fused_group_update(params, grads, mom, coeffs=c,
                                  head_coeffs=hc, head_mask=mask, impl=impl)
        _assert_trees_close(ref_p, p)
        _assert_trees_close(ref_v, v)


@pytest.mark.parametrize("strategy", ["fused", "scan"])
def test_g1_reduces_to_sgd_update(strategy):
    """Both strategies at g=1 must be plain synchronous sgd_update."""
    params = _tree(jax.random.PRNGKey(1))
    mom = jax.tree.map(lambda p: 0.2 * jnp.ones_like(p), params)
    grads = _grads(jax.random.PRNGKey(8), params, 1)
    g0 = jax.tree.map(lambda x: x[0], grads)
    ref_p, ref_v = sgd_update(params, g0, mom, lr=0.03, momentum=0.9,
                              weight_decay=1e-4)
    if strategy == "scan":
        p, v = scan_grouped_update(params, grads, mom, lr=0.03, momentum=0.9,
                                   weight_decay=1e-4)
    else:
        p, v = fused_group_update(
            params, grads, mom,
            coeffs=grouped_coeffs(1, lr=0.03, momentum=0.9, weight_decay=1e-4),
            head_coeffs=head_coeffs(1, lr=0.03, momentum=0.9,
                                    weight_decay=1e-4))
    _assert_trees_close(ref_p, p, rtol=1e-6, atol=1e-7)
    _assert_trees_close(ref_v, v, rtol=1e-6, atol=1e-7)


def test_head_mask_without_head_coeffs_raises():
    params = {"fc": jnp.ones((3,))}
    mom = jax.tree.map(jnp.zeros_like, params)
    grads = _grads(jax.random.PRNGKey(0), params, 2)
    with pytest.raises(ValueError, match="head_coeffs"):
        fused_group_update(params, grads, mom,
                           coeffs=grouped_coeffs(2, lr=0.1),
                           head_mask={"fc": True})


def test_momentum_dtype_roundtrip():
    """Reduced-dtype momentum buffers survive the fused path (fp32 accumulate,
    single cast back)."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(2), (33, 17))}
    mom = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.bfloat16), params)
    grads = _grads(jax.random.PRNGKey(3), params, 4)
    c = grouped_coeffs(4, lr=0.05, momentum=0.9)
    p, v = fused_group_update(params, grads, mom, coeffs=c)
    assert v["w"].dtype == jnp.bfloat16
    assert p["w"].dtype == params["w"].dtype
    ref_p, ref_v = scan_grouped_update(params, grads, mom, lr=0.05,
                                       momentum=0.9)
    # scan quantizes V to bf16 after EVERY sub-step and that error feeds
    # back into W; fused quantizes once — agreement only at bf16 resolution
    _assert_trees_close(ref_p, p, rtol=2e-2, atol=2e-2)
    _assert_trees_close(ref_v, v, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Pallas leaf kernel vs XLA oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1,), (7,), (128,), (300,), (37, 53),
                                   (2, 3, 5, 7), ()])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_leaf_matches_ref(shape, dtype):
    g = 4
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    w = jax.random.normal(ks[0], shape).astype(dtype)
    v = jax.random.normal(ks[1], shape).astype(dtype)
    gs = jax.random.normal(ks[2], (g,) + shape).astype(dtype)
    c = grouped_coeffs(g, lr=0.05, momentum=0.9, weight_decay=1e-4)
    rw, rv = fused_update_ref(w, v, gs, c)
    pw, pv = fused_update_pallas(w, v, gs, c)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(pw, np.float32),
                               np.asarray(rw, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(pv, np.float32),
                               np.asarray(rv, np.float32), rtol=tol, atol=tol)


def test_public_leaf_entry_point():
    """ops.fused_update (the jit'd per-leaf API) agrees across impls."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    w, v = jax.random.normal(ks[0], (40, 9)), jax.random.normal(ks[1], (40, 9))
    gs = jax.random.normal(ks[2], (4, 40, 9))
    c = grouped_coeffs(4, lr=0.05, momentum=0.9, weight_decay=1e-4)
    x = fused_update(w, v, gs, coeffs=c, impl="xla")
    p = fused_update(w, v, gs, coeffs=c, impl="pallas")
    _assert_trees_close(x, p, rtol=2e-6, atol=2e-6)


def test_pallas_block_sizes():
    """Every block_rows choice computes the same function."""
    g, shape = 2, (70, 90)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    w, v = jax.random.normal(ks[0], shape), jax.random.normal(ks[1], shape)
    gs = jax.random.normal(ks[2], (g,) + shape)
    c = grouped_coeffs(g, lr=0.1, momentum=0.5)
    ref = fused_update_pallas(w, v, gs, c, block_rows=256)
    for br in (8, 16, 64):
        out = fused_update_pallas(w, v, gs, c, block_rows=br)
        _assert_trees_close(ref, out, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# full train step: fused strategy vs scan strategy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g", [1, 2, 4])
def test_train_step_strategies_agree(g):
    wl = mlp_classify()
    params = wl.init(jax.random.PRNGKey(0))
    batches = wl.sample_batches(jax.random.PRNGKey(1), 3 * g, wl.batch_size)
    steps = {s: jax.jit(make_grouped_train_step(
        wl.loss_fn, num_groups=g, lr=0.05, momentum=0.9, weight_decay=1e-4,
        strategy=s)) for s in ("fused", "scan")}
    state = {s: (params, jax.tree.map(jnp.zeros_like, params))
             for s in steps}
    for t in range(3):
        batch = jax.tree.map(
            lambda x: x[t * g:(t + 1) * g], batches)  # (g, B, ...) per round
        losses = {}
        for s, fn in steps.items():
            p, m = state[s]
            p, m, losses[s] = fn(p, m, batch)
            state[s] = (p, m)
        np.testing.assert_allclose(float(losses["fused"]),
                                   float(losses["scan"]), rtol=1e-5)
    _assert_trees_close(state["fused"][0], state["scan"][0])
    _assert_trees_close(state["fused"][1], state["scan"][1])


def test_coeffs_no_momentum_no_decay_is_summed_lr():
    """mu=0, lambda=0: every group contributes exactly -eta (the scan just
    subtracts eta*g_i g times); momentum vector is -eta only for the last."""
    c = grouped_coeffs(4, lr=0.1)
    np.testing.assert_allclose(c.a, [-0.1] * 4, rtol=1e-12)
    np.testing.assert_allclose(c.b, [0.0, 0.0, 0.0, -0.1], atol=1e-12)
    assert c.cww == 1.0 and c.cvv == 0.0


def test_coeffs_momentum_powers():
    """lambda=0: a_i = -eta*(1-mu^{g-i})/(1-mu), b_i = -eta*mu^{g-1-i},
    V scaled by mu^g — the powers-of-mu form from the closed-form writeup."""
    g, eta, mu = 8, 0.05, 0.9
    c = grouped_coeffs(g, lr=eta, momentum=mu)
    for i in range(g):
        np.testing.assert_allclose(c.a[i], -eta * (1 - mu ** (g - i)) / (1 - mu),
                                   rtol=1e-12)
        np.testing.assert_allclose(c.b[i], -eta * mu ** (g - 1 - i), rtol=1e-12)
    np.testing.assert_allclose(c.cvv, mu ** g, rtol=1e-12)
    assert c.cww == 1.0 and c.cvw == 0.0


try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # property tests report as skipped; rest run
    st = None

if st is None:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_fused_matches_scan_property():
        pass
else:
    @settings(max_examples=15, deadline=None)
    @given(g=st.sampled_from([1, 2, 3, 5, 8]),
           mu=st.sampled_from([0.0, 0.3, 0.9]),
           wd=st.sampled_from([0.0, 1e-4, 1e-2]),
           lr=st.sampled_from([0.01, 0.1]),
           seed=st.integers(0, 2 ** 30))
    def test_fused_matches_scan_property(g, mu, wd, lr, seed):
        params = {"w": jax.random.normal(jax.random.PRNGKey(seed), (19, 23))}
        mom = jax.tree.map(lambda p: 0.05 * jnp.ones_like(p), params)
        grads = _grads(jax.random.PRNGKey(seed + 1), params, g)
        ref = scan_grouped_update(params, grads, mom, lr=lr, momentum=mu,
                                  weight_decay=wd)
        out = fused_group_update(
            params, grads, mom,
            coeffs=grouped_coeffs(g, lr=lr, momentum=mu, weight_decay=wd))
        _assert_trees_close(ref[0], out[0])
        _assert_trees_close(ref[1], out[1])
