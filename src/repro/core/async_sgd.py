"""Asynchronous (stale-gradient) SGD with compute groups.

Two implementations of the paper's execution strategy:

1. ``delayed_sgd_run`` — the Theorem-1-exact object: SGD where the gradient
   applied at step t was evaluated at ``W_{t-S}`` (S = g-1). Used by the
   statistical-efficiency experiments; carries an (S+1)-deep parameter
   history, so it is meant for small models on CPU.

2. ``grouped_train_step`` — the deployable SPMD step: each round, all g
   groups compute gradients at the round-start parameters **in parallel**
   (full hardware utilization on the mesh), then the g updates land with
   staleness 0..g-1 — the paper's Fig. 17(b) round-robin picture.
   ``head_filter`` implements the merged-FC optimization: head params see
   one averaged (zero-staleness) update each round.

   Because all g gradients are evaluated at round-start parameters, the g
   sequential momentum-SGD sub-steps form a linear recurrence with a
   closed-form solution (optim/closed_form.py). The default
   ``strategy="fused"`` applies that closed form in ONE pass over the
   parameters (kernels/fused_update); ``strategy="scan"`` keeps the
   literal O(g) sequential application as the semantic reference.

Both reduce exactly to synchronous data-parallel SGD at g=1.

``repro.exec.replay`` generalizes (1) from one fixed staleness S to
per-commit staleness along an arbitrary recorded ``EventTrace`` (ring-
buffered parameter history); the deterministic round-robin traces reduce
it back to these two implementations — the conformance contract pinned by
``tests/test_exec_replay.py``.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.fused_update.ops import fused_group_update
from repro.optim.closed_form import (_weight_scales, grouped_coeffs,
                                     head_coeffs)


# ---------------------------------------------------------------------------
# 1. Exact delayed SGD (Theorem-1 semantics), for SE experiments
# ---------------------------------------------------------------------------

def delayed_sgd_run(loss_fn: Callable, params, batches, *, staleness: int,
                    lr: float, momentum: float = 0.0, weight_decay: float = 0.0,
                    record_params: bool = False):
    """Run ``T`` delayed-SGD steps (T = leading dim of ``batches``).

    Update:  V_{t+1} = mu V_t - eta grad(W_{t-S});  W_{t+1} = W_t + V_{t+1}.
    For t < S the oldest available parameters are used (cold history).

    Returns (final_params, losses (T,), params_trace or None).
    """
    S = staleness
    flat, tree = jax.tree.flatten(params)
    hist = [jnp.stack([f] * (S + 1)) for f in flat]     # ring of last S+1 params
    mom = [jnp.zeros_like(f) for f in flat]

    def step(carry, batch):
        hist, mom, t = carry
        # oldest params in the ring = W_{t-S} (clamped during cold history)
        idx = jnp.where(t >= S, (t - S) % (S + 1), 0)
        stale = tree.unflatten([h[idx] for h in hist])
        cur = tree.unflatten([h[t % (S + 1)] for h in hist])
        loss, grads = jax.value_and_grad(loss_fn)(stale, batch)
        gflat = jax.tree.leaves(grads)
        new_flat, new_mom = [], []
        for c, g, v in zip(jax.tree.leaves(cur), gflat, mom):
            if weight_decay:
                g = g + weight_decay * c
            v_new = momentum * v - lr * g
            new_flat.append(c + v_new)
            new_mom.append(v_new)
        new_hist = [h.at[(t + 1) % (S + 1)].set(nf)
                    for h, nf in zip(hist, new_flat)]
        out = (tree.unflatten(new_flat) if record_params else None, loss)
        return (new_hist, new_mom, t + 1), out

    (hist, mom, t), (trace, losses) = jax.lax.scan(
        step, (hist, mom, jnp.int32(0)), batches)
    final = tree.unflatten([h[t % (S + 1)] for h in hist])
    return final, losses, trace


# ---------------------------------------------------------------------------
# 2. Deployable SPMD grouped step
# ---------------------------------------------------------------------------

def scan_grouped_update(params, grads, mom_buf, *, lr: float, momentum: float,
                        weight_decay: float = 0.0, head_mask=None,
                        group_weights: Optional[Sequence[float]] = None):
    """Reference O(g) update application: the literal sequential scan over
    the g sub-steps (plus the merged-FC head update). ``grads`` carries a
    leading (g, ...) group axis per leaf. Returns (params, mom_buf).
    Argument order matches ``sgd_update`` and ``fused_group_update`` so the
    strategies are drop-in interchangeable.

    ``group_weights`` (unequal batch shares, ``cluster.allocator``): group
    i's gradient is pre-scaled by ``g * w_i / sum(w)`` before every use, so
    the head sees the share-weighted average and sub-step i a share-scaled
    step. Uniform weights scale by exactly 1.0 — bitwise the unweighted
    path.

    Kept as the semantic oracle for the fused closed-form path — it pays
    g read-modify-write passes over every leaf and a per-leaf fp32 cast
    round-trip per sub-step, which is exactly what fused_group_update
    collapses.
    """
    g = jax.tree.leaves(grads)[0].shape[0]
    if head_mask is None:
        head_mask = jax.tree.map(lambda _: False, params)
    scales = _weight_scales(g, group_weights)
    if scales is not None:
        sarr = jnp.asarray(scales, jnp.float32)
        grads = jax.tree.map(
            lambda gr: gr * sarr.reshape((g,) + (1,) * (gr.ndim - 1)).astype(
                gr.dtype), grads)

    # g == 1 deliberately takes the same one-iteration lax.scan path below
    # instead of shortcutting to sgd_update: one code path for every g, so
    # the engine's spmd/reference conformance suite exercises exactly what
    # g>1 runs (the shortcut compiled its weight-decay arithmetic with a
    # different FMA contraction than the scan body in some surrounding
    # programs — a one-ulp context dependence the single path avoids at
    # the suite's weight_decay=0 operating point; see docs/engine.md).

    # merged-FC head: single synchronous (share-weighted) averaged update
    # per round — with pre-scaled gradients the plain mean is that average
    head_grads = jax.tree.map(lambda gr: gr.mean(axis=0), grads)

    def upd_leaf(p, gg, v):
        g32 = gg.astype(jnp.float32)
        if weight_decay:
            g32 = g32 + weight_decay * p.astype(jnp.float32)
        v_new = momentum * v.astype(jnp.float32) - lr * g32
        return ((p.astype(jnp.float32) + v_new).astype(p.dtype),
                v_new.astype(v.dtype))

    def apply_one(carry, i):
        p, v = carry
        gi = jax.tree.map(lambda gr: gr[i], grads)
        # backbone: apply group-i gradient; head: untouched this sub-step
        new = jax.tree.map(
            lambda m, pp, gg, vv: (pp, vv) if m else upd_leaf(pp, gg, vv),
            head_mask, p, gi, v)
        p = jax.tree.map(lambda t: t[0], new,
                         is_leaf=lambda t: isinstance(t, tuple))
        v = jax.tree.map(lambda t: t[1], new,
                         is_leaf=lambda t: isinstance(t, tuple))
        return (p, v), None

    (params, mom_buf), _ = jax.lax.scan(
        apply_one, (params, mom_buf), jnp.arange(g))
    # head update (zero-staleness, merged FC), once per round
    new = jax.tree.map(
        lambda m, pp, gg, vv: upd_leaf(pp, gg, vv) if m else (pp, vv),
        head_mask, params, head_grads, mom_buf)
    params = jax.tree.map(lambda t: t[0], new,
                          is_leaf=lambda t: isinstance(t, tuple))
    mom_buf = jax.tree.map(lambda t: t[1], new,
                           is_leaf=lambda t: isinstance(t, tuple))
    return params, mom_buf


def apply_grouped_update(params, grads, mom_buf, *, strategy: str, lr: float,
                         momentum: float, weight_decay: float = 0.0,
                         head_mask=None,
                         group_weights: Optional[Sequence[float]] = None,
                         update_impl: str = "xla",
                         coeffs=None, hcoeffs=None):
    """Apply one round of grouped updates (``grads`` leading axis = g) via
    either strategy — the single update-application entry point shared by
    ``make_grouped_train_step`` and the execution engine
    (``repro.engine``). Returns ``(params, mom_buf)``.

    ``coeffs`` / ``hcoeffs`` (``optim.closed_form``) may be precomputed by
    the caller for the fused path; when omitted they are derived here from
    (g, lr, momentum, weight_decay, group_weights).
    """
    if strategy == "scan":
        return scan_grouped_update(
            params, grads, mom_buf, lr=lr, momentum=momentum,
            weight_decay=weight_decay, head_mask=head_mask,
            group_weights=group_weights)
    if strategy != "fused":
        raise ValueError(f"unknown strategy {strategy!r}")
    g = jax.tree.leaves(grads)[0].shape[0]
    if coeffs is None:
        coeffs = grouped_coeffs(g, lr=lr, momentum=momentum,
                                weight_decay=weight_decay,
                                group_weights=group_weights)
    if hcoeffs is None:
        hcoeffs = head_coeffs(g, lr=lr, momentum=momentum,
                              weight_decay=weight_decay,
                              group_weights=group_weights)
    return fused_group_update(params, grads, mom_buf, coeffs=coeffs,
                              head_coeffs=hcoeffs, head_mask=head_mask,
                              impl=update_impl)


def head_mask_tree(params, head_filter: Optional[Callable]):
    """Python-bool tree marking merged-FC head leaves (True) — the mask
    consumed by both update strategies."""
    if head_filter is None:
        return jax.tree.map(lambda _: False, params)
    return jax.tree_util.tree_map_with_path(
        lambda path, _: bool(head_filter(path)), params)


def make_grouped_train_step(loss_fn: Callable, *, num_groups: int, lr: float,
                            momentum: float, weight_decay: float = 0.0,
                            head_filter: Optional[Callable] = None,
                            grad_accum: int = 1, strategy: str = "fused",
                            update_impl: str = "xla",
                            group_weights: Optional[Sequence[float]] = None):
    """Build ``step(params, mom_buf, batches) -> (params, mom_buf, loss)``.

    ``batches``: pytree with leading axis ``(g, ...)`` (one microbatch per
    group, see ``group_batch_split``); with grad_accum > 1 the per-group
    batch has a further leading accumulation axis ``(g, A, ...)``.

    ``head_filter(path) -> bool`` marks head ("FC-phase") params: merged-FC
    semantics — their g per-group gradients are averaged and applied once
    per round (zero staleness), while backbone params receive the g updates
    with staleness 0..g-1.

    ``strategy``: "fused" (default) applies the closed form of the g
    sub-steps in one fused pass; "scan" is the literal sequential
    reference. ``update_impl``: "xla" or "pallas" leaf kernel for the
    fused path (compiled on TPU, interpreted elsewhere).

    ``group_weights``: per-group batch shares from a heterogeneous
    allocation (``cluster.allocator.Allocation.weights``). Gradients are
    weighted ``g * w_i / sum(w)`` per sub-step and ``w_i / sum(w)`` in the
    merged-FC head average; uniform weights reproduce the equal-share path
    exactly.
    """
    if strategy not in ("fused", "scan"):
        raise ValueError(f"unknown strategy {strategy!r}")
    if group_weights is not None:
        group_weights = tuple(float(w) for w in group_weights)
    g = num_groups
    coeffs = grouped_coeffs(g, lr=lr, momentum=momentum,
                            weight_decay=weight_decay,
                            group_weights=group_weights)
    hcoeffs = head_coeffs(g, lr=lr, momentum=momentum,
                          weight_decay=weight_decay,
                          group_weights=group_weights)

    def per_group_grad(params, batch):
        if grad_accum == 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def acc_step(carry, micro):
            l, gr = jax.value_and_grad(loss_fn)(params, micro)
            return (carry[0] + l, jax.tree.map(jnp.add, carry[1], gr)), None
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (l, gr), _ = jax.lax.scan(acc_step, (jnp.float32(0.0), zeros), batch)
        return l / grad_accum, jax.tree.map(lambda x: x / grad_accum, gr)

    def step(params, mom_buf, batches):
        # all group gradients at round-start params, in parallel
        losses, grads = jax.vmap(per_group_grad, in_axes=(None, 0))(params, batches)
        params, mom_buf = apply_grouped_update(
            params, grads, mom_buf, strategy=strategy, lr=lr,
            momentum=momentum, weight_decay=weight_decay,
            head_mask=head_mask_tree(params, head_filter),
            group_weights=group_weights, update_impl=update_impl,
            coeffs=coeffs, hcoeffs=hcoeffs)
        return params, mom_buf, losses.mean()

    return step
