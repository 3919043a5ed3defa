"""Pallas TPU kernel: fused grouped momentum-SGD parameter update.

Applies the closed form of g sequential sub-steps (optim/closed_form.py)
to one parameter leaf in a single pass:

    W_new = cww*W + cwv*V + sum_i a_i * G[i]
    V_new = cvw*W + cvv*V + sum_i b_i * G[i]

The scan-based reference reads and writes every (W, V) leaf g times and
round-trips each leaf through an fp32 copy per sub-step. Here each grid
step loads one (block_rows, 128) tile of W/V plus the matching (g, ...)
gradient tile into VMEM, accumulates the weighted combination in fp32
*in registers/VMEM*, and writes the tile back once — HBM traffic drops
from O(g*(|W|+|V|)) to O(|W|+|V|) + the unavoidable g*|G| gradient reads.

Leaves of arbitrary shape are flattened and zero-padded to (rows, 128)
lane tiles; coefficients are compile-time Python floats (closed over the
static hyperparameters), so no scalar prefetch is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.optim.closed_form import GroupedCoeffs

LANE = 128       # TPU lane width (last dim of every tile)


def _sublane(*dtypes) -> int:
    """Native TPU sublane multiple: 8 rows for 4-byte, 16 for 2-byte,
    32 for 1-byte dtypes. Blocks are shared across W/V/G, so take the
    strictest requirement."""
    return max(max(8, 32 // jnp.dtype(d).itemsize) for d in dtypes)


def _kernel(w_ref, v_ref, g_ref, wo_ref, vo_ref, *, coeffs: GroupedCoeffs):
    w = w_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    gs = g_ref[...].astype(jnp.float32)        # (g, block_rows, LANE)
    acc_w = coeffs.cww * w + coeffs.cwv * v
    acc_v = coeffs.cvw * w + coeffs.cvv * v
    for i in range(coeffs.num_groups):         # static unroll, g is small
        acc_w = acc_w + coeffs.a[i] * gs[i]
        acc_v = acc_v + coeffs.b[i] * gs[i]
    wo_ref[...] = acc_w.astype(wo_ref.dtype)
    vo_ref[...] = acc_v.astype(vo_ref.dtype)


def fused_update_pallas(w: jax.Array, v: jax.Array, gstack: jax.Array,
                        coeffs: GroupedCoeffs, *, block_rows: int = 256):
    """One leaf or one bucket slab: w/v any shape (a flat (n,) packing of
    several leaves works — everything is flattened to lane tiles anyway),
    gstack (g, *w.shape). Returns (w_new, v_new).

    Compiled on a TPU, interpreted elsewhere
    (``repro.kernels.interpret_mode``); the XLA reference in ref.py is the
    production non-TPU path.
    """
    g = gstack.shape[0]
    if g != coeffs.num_groups:
        raise ValueError(f"gstack has {g} groups, coeffs {coeffs.num_groups}")
    n = w.size
    sub = _sublane(w.dtype, v.dtype, gstack.dtype)
    rows = max(1, -(-n // LANE))
    br = max(sub, min(block_rows, -(-rows // sub) * sub))
    br = (br // sub) * sub
    rows_p = -(-rows // br) * br
    pad = rows_p * LANE - n
    w2 = jnp.pad(w.reshape(-1), (0, pad)).reshape(rows_p, LANE)
    v2 = jnp.pad(v.reshape(-1), (0, pad)).reshape(rows_p, LANE)
    g2 = jnp.pad(gstack.reshape(g, -1),
                 ((0, 0), (0, pad))).reshape(g, rows_p, LANE)

    wn, vn = pl.pallas_call(
        functools.partial(_kernel, coeffs=coeffs),
        grid=(rows_p // br,),
        in_specs=[
            pl.BlockSpec((br, LANE), lambda r: (r, 0)),
            pl.BlockSpec((br, LANE), lambda r: (r, 0)),
            pl.BlockSpec((g, br, LANE), lambda r: (0, r, 0)),
        ],
        out_specs=[pl.BlockSpec((br, LANE), lambda r: (r, 0)),
                   pl.BlockSpec((br, LANE), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_p, LANE), w.dtype),
                   jax.ShapeDtypeStruct((rows_p, LANE), v.dtype)],
        interpret=kernels.interpret_mode(),
    )(w2, v2, g2)
    return (wn.reshape(-1)[:n].reshape(w.shape),
            vn.reshape(-1)[:n].reshape(v.shape))
