"""Mesh-sharded grouped step — the paper's compute groups as real SPMD.

The mesh is a ``("group", "data", "mp")`` split of the device pool:
g groups of k workers of mp model-parallel devices each
(``launch.mesh.make_group_mesh``). The global batch is sharded over the
first two axes, every worker computes the gradient of its own microbatch
shard, and the per-group gradient is the mean of the group's k shard
gradients — synchronous data parallelism *within* a group, the
round-robin staleness-0..g-1 grouped update *across* groups (applied
replicated on every device, so parameters never diverge).

Model-parallel storage (``mp > 1``): parameters and momentum are STORED
sharded over the "mp" axis per the PartitionSpecs of
``sharding.rules.engine_param_specs`` (explicit regex rules →
TENSOR_PREF name table → auto-derived trailing divisible dim). The
compute itself stays full-parameter: each device ``all_gather``s the
full parameters from the mp shards (tiled — pure data movement, so the
gathered bits equal the unsharded bits), runs forward/backward on its
microbatch (replicated across mp), then slices the gradient back to its
own mp shard before the exchange. The grouped update is elementwise, so
updating the local shard with the shard of the gradient is bitwise the
shard of the full update — which is how sharded ≡ unsharded stays a
BITWISE identity (pinned by tests/test_engine.py at
(g, mp) ∈ {1,2} × {1,2}). Data/group collectives carry 1/mp of the
gradient bytes; ``mp == 1`` traces the exact pre-mp graph (no gather,
no slice, replicated ``P()`` specs).

Reproducibility contract (pinned by ``tests/test_engine.py``): the
cross-device combination uses ``all_gather`` + a *local* mean on every
device instead of ``psum``. A psum's reduction grouping is backend-chosen
and does not bit-match a single-device reduction; gathering moves bits
unchanged, and the local mean is then the very same reduction the
single-device reference performs. The cost is an O(k) instead of
O(log k) gradient exchange — at the CPU-test and small-cluster scales the
engine targets, bitwise run-anywhere reproducibility is worth more than
the bandwidth (the production dry-run path keeps its psum-based
GSPMD lowering).

Overlapped bucketed exchange (``bucket_bytes > 0``, the default): the
gradient tree is cut into flat slabs (``engine.buckets``) and each slab's
``all_gather("data")`` + ``all_gather("group")`` depends only on its own
leaves, so XLA's async collective pairs start as soon as a bucket's last
gradient is produced and run concurrently with the rest of the backward
pass — the exchange comes off the critical path instead of serializing
after it. The closed-form grouped update is fused into each bucket's
gather epilogue (``kernels.fused_update.fused_bucket_update`` on the
slabs). Bucketing only *reorders independent gathers* and packs leaves by
pure data movement, so the result stays bitwise equal to the whole-tree
step and to ``make_reference_grouped_step``. ``bucket_bytes = 0`` keeps
the legacy whole-tree arm (one gather pair per leaf, applied after the
full backward) — the head-to-head baseline in ``benchmarks/run.py``.

Donation audit: every parameter/momentum output carries an additive
``- tie`` term where ``tie = (0.0 * (loss + sum_buckets sum(raw_grads)))²``
— always ``+0.0`` for finite inputs (squaring kills a possible ``-0.0``,
and subtracting ``+0.0`` is a bitwise identity for every float *including*
``-0.0``), yet never constant-foldable because it propagates NaN/Inf.
The term gives XLA's copy-insertion pass an *arithmetic* dependency from
every reader of the round-start parameters (the backward pass and the
loss) to every parameter write, so the donated input buffers can be
updated in place: the compiled donating step contains no parameter-sized
``copy`` instructions (pinned by tests/test_engine.py). A plain
``optimization_barrier`` does not work here — XLA CPU's copy elision
ignores barrier-induced ordering, and ordering paths that run through
async collective pairs get no credit either, which is why the tie is
computed from the *raw pre-gather* gradient slabs.

``make_reference_grouped_step`` is the single-device twin: ``lax.map``
over the same (g, k) shard structure — unbatched per-shard gradients in
shard order, identical means, identical update — so the SPMD step must
bit-match it leaf for leaf. (A vmap-batched gradient does NOT bit-match
an unbatched one for all models — scatter-add ordering in embedding
backward passes differs — which is why the reference maps over shards
instead of vmapping them.)
"""
from __future__ import annotations

import warnings
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.async_sgd import apply_grouped_update, head_mask_tree
from repro.engine.buckets import assign_buckets, pack_bucket, unpack_bucket
from repro.kernels.fused_update.ops import fused_bucket_update
from repro.optim.closed_form import grouped_coeffs, head_coeffs

#: default per-bucket slab size target (bytes) for the overlapped
#: exchange; 0 selects the legacy whole-tree arm
DEFAULT_BUCKET_BYTES = 4 << 20


class StrandedDevicesWarning(UserWarning):
    """The chosen within-group width k leaves device slots idle because
    nothing larger divides the per-group microbatch."""


def choose_data_parallel(per_group_batch: int, max_k: int, *,
                         warn: bool = True) -> int:
    """Largest within-group data-parallel width k <= max_k that divides the
    per-group microbatch. Falls back to k=1 when nothing divides; any
    k < max_k strands ``max_k - k`` device slots per group — warned here
    (``StrandedDevicesWarning``) and surfaced in ``Engine`` telemetry."""
    if per_group_batch < 1 or max_k < 1:
        return 1
    k = 1
    for cand in range(min(max_k, per_group_batch), 0, -1):
        if per_group_batch % cand == 0:
            k = cand
            break
    if warn and k < max_k:
        warnings.warn(StrandedDevicesWarning(
            f"per-group batch {per_group_batch} admits data-parallel "
            f"width k={k} < {max_k}: {max_k - k} device slot(s) per group "
            "stranded (pick a batch divisible by the per-group device "
            "count to use the full mesh)"), stacklevel=2)
    return k


def device_batch_split(group_batch, k: int):
    """(g, b, ...) leaves -> (g, k, b/k, ...): one shard per mesh device."""
    def split(x):
        g, b = x.shape[0], x.shape[1]
        if b % k:
            raise ValueError(f"per-group batch {b} not divisible by k={k}")
        return x.reshape(g, k, b // k, *x.shape[2:])
    return jax.tree.map(split, group_batch)


def _donation_tie(loss, raw_slabs):
    """The ``+0.0`` ordering term of the donation audit (module doc):
    arithmetically depends on the loss and every raw pre-gather gradient,
    is exactly ``+0.0`` for finite inputs, and propagates NaN/Inf (so XLA
    cannot fold it away)."""
    acc = loss.astype(jnp.float32)
    for slab in raw_slabs:
        acc = acc + jnp.sum(slab).astype(jnp.float32)
    t = jnp.float32(0.0) * acc
    return t * t          # squaring forces +0.0 (never -0.0)


def make_spmd_grouped_step(loss_fn: Callable, mesh: Mesh, *, lr: float,
                           momentum: float, weight_decay: float = 0.0,
                           strategy: str = "fused",
                           head_filter: Optional[Callable] = None,
                           group_weights: Optional[Sequence[float]] = None,
                           update_impl: str = "xla",
                           bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                           sharding_rules=None):
    """Build the mesh-sharded ``step(params, mom, device_batch)``.

    ``device_batch`` leaves carry a leading (g, k, b/k) layout
    (``device_batch_split``); params/momentum enter replicated over
    "group"/"data" (and, when the mesh carries an "mp" axis wider than 1,
    sharded over "mp" per ``sharding.rules.engine_param_specs``) and
    leave the same way — the grouped update runs identically on every
    worker from the all-gathered (g, ...) gradient stack. Returns
    ``(params, mom, losses)`` with ``losses`` the (g, k) per-shard loss
    array — the scalar mean is taken on the host (deterministic float64)
    so the reported loss bit-matches the reference path too, instead of
    depending on how XLA fuses the final reduction.

    ``bucket_bytes``: slab size target of the overlapped bucketed
    exchange (module doc); 0 selects the legacy whole-tree arm. With
    ``mp > 1`` the buckets pack the LOCAL gradient shards (slab bytes =
    local shard bytes) and the donation tie is computed from those raw
    local slabs, so the in-place update of the donated shard buffers
    stays ordered against the backward pass.

    ``sharding_rules``: optional explicit ``(regex-path-window, spec)``
    rules forwarded to ``engine_param_specs`` (first match wins; the
    TENSOR_PREF table and auto-derivation cover unmatched leaves).

    ``step.place(params, mom)`` puts both trees where the step keeps
    them (committed ``NamedSharding``s on ``mesh``). Calling the step
    on placed trees from the first round on makes every round's inputs
    look alike to ``jit``: unplaced first-round inputs would compile the
    step a second time on round two, when the outputs of round one come
    back placed.
    """
    g, k = mesh.shape["group"], mesh.shape["data"]
    mp = int(mesh.shape.get("mp", 1))
    bucket_bytes = int(bucket_bytes)
    if strategy == "fused":
        coeffs = grouped_coeffs(g, lr=lr, momentum=momentum,
                                weight_decay=weight_decay,
                                group_weights=group_weights)
        hcoeffs = head_coeffs(g, lr=lr, momentum=momentum,
                              weight_decay=weight_decay,
                              group_weights=group_weights)
    else:
        coeffs = hcoeffs = None

    def specs_of(params):
        if mp == 1:
            return P()
        from repro.sharding.rules import engine_param_specs
        return engine_param_specs(params, mesh, rules=sharding_rules)

    def place(params, mom_buf):
        specs = specs_of(params)
        sh = (NamedSharding(mesh, specs) if isinstance(specs, P) else
              jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                           is_leaf=lambda x: isinstance(x, P)))
        return jax.device_put(params, sh), jax.device_put(mom_buf, sh)

    def step(params, mom_buf, dbatch):
        head_mask = head_mask_tree(params, head_filter)
        tdef = jax.tree.structure(params)
        param_specs = specs_of(params)
        if mp > 1:
            from repro.sharding.rules import spec_mp_dim
            mp_dims = [spec_mp_dim(s, "mp") for s in
                       jax.tree.leaves(param_specs,
                                       is_leaf=lambda x: isinstance(x, P))]
        else:
            mp_dims = None

        def shard_fn(p, v, bt):
            local = jax.tree.map(lambda t: t[0, 0], bt)   # this device's shard
            if mp > 1:
                # gather the full parameters from the mp shards: tiled
                # all_gather is pure data movement, so the gathered leaf
                # is bit-identical to the unsharded one (module doc)
                full_p = jax.tree.unflatten(tdef, [
                    t if d is None else
                    jax.lax.all_gather(t, "mp", axis=d, tiled=True)
                    for t, d in zip(jax.tree.leaves(p), mp_dims)])
            else:
                full_p = p
            loss, grad = jax.value_and_grad(loss_fn)(full_p, local)
            if mp > 1:
                # slice the full-parameter gradient back to this device's
                # mp shard; everything downstream (mean over "data",
                # stack over "group", elementwise update) commutes with
                # the slice, so the updated shard is bitwise the shard of
                # the full update
                i_mp = jax.lax.axis_index("mp")

                def to_shard(t, d):
                    if d is None:
                        return t
                    size = t.shape[d] // mp
                    return jax.lax.dynamic_slice_in_dim(
                        t, i_mp * size, size, axis=d)

                grad = jax.tree.unflatten(tdef, [
                    to_shard(t, d)
                    for t, d in zip(jax.tree.leaves(grad), mp_dims)])
            # one collective for the loss board: a single gather over both
            # mesh axes reshapes bit-identically to the old nested
            # all_gather("data") + all_gather("group") pair
            losses = jax.lax.all_gather(
                loss, ("group", "data")).reshape(g, k)

            if bucket_bytes <= 0:
                # legacy whole-tree arm: gather every leaf after the full
                # backward pass (the pre-overlap baseline, kept for the
                # head-to-head benchmark)
                grad = jax.tree.map(
                    lambda t: jax.lax.all_gather(t, "data").mean(axis=0),
                    grad)
                grad = jax.tree.map(
                    lambda t: jax.lax.all_gather(t, "group"), grad)
                p, v = apply_grouped_update(
                    p, grad, v, strategy=strategy, lr=lr, momentum=momentum,
                    weight_decay=weight_decay, head_mask=head_mask,
                    group_weights=group_weights, update_impl=update_impl,
                    coeffs=coeffs, hcoeffs=hcoeffs)
                return p, v, losses

            # ---- overlapped bucketed exchange ----
            flat_g, tree = jax.tree.flatten(grad)
            flat_p = tree.flatten_up_to(p)
            flat_v = tree.flatten_up_to(v)
            flat_m = tree.flatten_up_to(head_mask)
            buckets = assign_buckets(flat_g, flat_m, bucket_bytes)
            raw_slabs = [pack_bucket(b, flat_g) for b in buckets]
            # each bucket's gather pair depends only on its own slab, so
            # the async collectives overlap the remaining backward compute
            gathered = []
            for slab in raw_slabs:
                s = jax.lax.all_gather(slab, "data").mean(axis=0)
                gathered.append(jax.lax.all_gather(s, "group"))   # (g, n)
            # the tie is applied to the update's *inputs* (not outputs):
            # the in-place write the donated buffers receive is the update
            # itself — an output-side tie would leave that write unordered
            # against the forward/backward reads of the old values (the
            # lax.scan carry of the scan strategy exhibits exactly that as
            # a residual copy)
            tie = _donation_tie(loss, raw_slabs)
            flat_p = [t - tie for t in flat_p]
            flat_v = [t - tie for t in flat_v]

            new_p = list(flat_p)
            new_v = list(flat_v)
            if strategy == "fused":
                # update fused into each bucket's gather epilogue, on the
                # flat slabs; unpack (slice+reshape) back to leaves
                for b, gs in zip(buckets, gathered):
                    wn, vn = fused_bucket_update(
                        pack_bucket(b, flat_p), pack_bucket(b, flat_v), gs,
                        coeffs=hcoeffs if b.is_head else coeffs,
                        impl=update_impl)
                    for i, w_leaf, v_leaf in zip(b.indices,
                                                 unpack_bucket(b, wn),
                                                 unpack_bucket(b, vn)):
                        new_p[i] = w_leaf
                        new_v[i] = v_leaf
            else:
                # scan strategy: buckets only change the gather
                # granularity — reassemble the per-leaf (g, ...) stacks
                # and run the literal sequential oracle unchanged
                flat_stacks = list(flat_g)
                for b, gs in zip(buckets, gathered):
                    for i, stack in zip(b.indices, unpack_bucket(b, gs)):
                        flat_stacks[i] = stack
                p2, v2 = apply_grouped_update(
                    tree.unflatten(flat_p), tree.unflatten(flat_stacks),
                    tree.unflatten(flat_v), strategy=strategy,
                    lr=lr, momentum=momentum, weight_decay=weight_decay,
                    head_mask=head_mask, group_weights=group_weights,
                    update_impl=update_impl)
                new_p = tree.flatten_up_to(p2)
                new_v = tree.flatten_up_to(v2)
            return tree.unflatten(new_p), tree.unflatten(new_v), losses

        return jax.shard_map(
            shard_fn, mesh=mesh, check_vma=False,
            in_specs=(param_specs, param_specs, P("group", "data")),
            out_specs=(param_specs, param_specs, P()))(params, mom_buf,
                                                       dbatch)

    step.mesh_shape = (g, k, mp)
    step.bucket_bytes = bucket_bytes
    step.place = place
    return step


def make_reference_grouped_step(loss_fn: Callable, g: int, k: int, *,
                                lr: float, momentum: float,
                                weight_decay: float = 0.0,
                                strategy: str = "fused",
                                head_filter: Optional[Callable] = None,
                                group_weights: Optional[Sequence[float]] = None,
                                update_impl: str = "xla",
                                bucket_bytes: int = DEFAULT_BUCKET_BYTES):
    """Single-device reference of the SPMD step: the same (g, k) shard
    structure executed sequentially (``lax.map`` over shards), the same
    shard-mean and update. Bitwise target of ``make_spmd_grouped_step``
    at EVERY ``bucket_bytes`` (accepted and ignored here — bucketing is
    a pure communication-schedule change).
    """
    del bucket_bytes

    def step(params, mom_buf, dbatch):
        flat = jax.tree.map(
            lambda t: t.reshape((g * k,) + t.shape[2:]), dbatch)
        losses, grads = jax.lax.map(
            lambda bt: jax.value_and_grad(loss_fn)(params, bt), flat)
        grads = jax.tree.map(
            lambda t: t.reshape((g, k) + t.shape[1:]).mean(axis=1), grads)
        params_n, mom_n = apply_grouped_update(
            params, grads, mom_buf, strategy=strategy, lr=lr,
            momentum=momentum, weight_decay=weight_decay,
            head_mask=head_mask_tree(params, head_filter),
            group_weights=group_weights, update_impl=update_impl)
        return params_n, mom_n, losses.reshape(g, k)

    step.mesh_shape = (g, k)
    return step


def group_mesh_devices(g: int, k: int, mp: int = 1):
    """The first g*k*mp local devices as a (g, k, mp) array for mesh
    construction (``launch.mesh.make_group_mesh``)."""
    n = g * k * mp
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"need {n} devices for a ({g},{k},{mp}) group "
                         f"mesh; have {len(devs)} (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N on CPU)")
    return np.array(devs[:n]).reshape(g, k, mp)
