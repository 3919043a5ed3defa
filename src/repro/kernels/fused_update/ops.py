"""Public wrappers for the fused grouped update: a jit'd per-leaf entry
point, the single-traversal tree-level update, and the per-bucket slab
entry used by the overlapped SPMD exchange (``engine.spmd``)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.fused_update.fused_update import fused_update_pallas
from repro.kernels.fused_update.ref import fused_update_ref
from repro.optim.closed_form import GroupedCoeffs


def _leaf_update(w, v, gstack, coeffs: GroupedCoeffs, *, impl: str,
                 block_rows: int):
    if impl == "pallas":
        return fused_update_pallas(w, v, gstack, coeffs,
                                   block_rows=block_rows)
    if impl != "xla":
        raise ValueError(f"unknown impl {impl!r}")
    return fused_update_ref(w, v, gstack, coeffs)


@functools.partial(jax.jit,
                   static_argnames=("coeffs", "impl", "block_rows"))
def fused_update(w, v, gstack, *, coeffs: GroupedCoeffs, impl: str = "xla",
                 block_rows: int = 256):
    """One leaf. impl='pallas' runs the TPU kernel (compiled on TPU,
    interpret mode elsewhere); impl='xla' the reference combination
    (production path off-TPU)."""
    return _leaf_update(w, v, gstack, coeffs, impl=impl,
                        block_rows=block_rows)


def fused_bucket_update(w_slab, v_slab, gstack, *, coeffs: GroupedCoeffs,
                        impl: str = "xla", block_rows: int = 256):
    """Per-bucket slab update for the overlapped SPMD exchange: ``w_slab``
    / ``v_slab`` are (n,) flat packings of a bucket's leaves
    (``engine.buckets.pack_bucket``), ``gstack`` the gathered (g, n)
    gradient slab. Both the Pallas kernel and the XLA reference are
    shape-agnostic elementwise combinations, so the slab result is
    bit-identical to the per-leaf updates it replaces. Not jitted: the
    caller traces inside ``shard_map``."""
    return _leaf_update(w_slab, v_slab, gstack, coeffs, impl=impl,
                        block_rows=block_rows)


def fused_group_update(params, grads, mom_buf, *, coeffs: GroupedCoeffs,
                       head_coeffs: GroupedCoeffs = None, head_mask=None,
                       impl: str = "xla", block_rows: int = 256):
    """Whole-tree fused update in ONE traversal.

    grads: same tree as params with a leading (g, ...) group axis per leaf.
    head_mask: optional tree of Python bools — True leaves (merged-FC head)
    use ``head_coeffs`` (single averaged zero-staleness update), the rest
    ``coeffs`` (g sequential sub-steps, collapsed). Returns
    (new_params, new_mom).
    """
    flat_w, tree = jax.tree.flatten(params)
    # flatten_up_to validates grads/mom/mask against the params structure
    # (a bare zip would silently mis-pair leaves on tree mismatch)
    flat_g = tree.flatten_up_to(grads)
    flat_v = tree.flatten_up_to(mom_buf)
    flat_m = (tree.flatten_up_to(head_mask) if head_mask is not None
              else [False] * len(flat_w))
    new_w, new_v = [], []
    for w, g, v, is_head in zip(flat_w, flat_g, flat_v, flat_m):
        if is_head and head_coeffs is None:
            raise ValueError("head_mask marks head leaves but head_coeffs "
                             "was not provided")
        c = head_coeffs if is_head else coeffs
        wn, vn = _leaf_update(w, v, g, c, impl=impl, block_rows=block_rows)
        new_w.append(wn)
        new_v.append(vn)
    return tree.unflatten(new_w), tree.unflatten(new_v)
