"""Backward pass of the lowering conv as batched GEMMs (paper §III applied
to backprop; docs/lowering_conv.md).

Both gradients are GEMMs over the *same* lowered patch matrix the forward
already built:

  wgrad   dW_hat = lowered(x)^T @ dY_hat          one (K, M) x (M, Cout) GEMM
  dgrad   dCols  = dY_hat @ K_hat^T               one (M, Cout) x (Cout, K) GEMM
          dX     = col2im(dCols)                  scatter of the K = kh*kw*Cin
                                                  patch columns back to pixels

The wgrad consumes the forward's lowered residual instead of re-lowering —
the paper's trade-memory-for-GEMM move applied to the backward pass. Two
implementations of each: an XLA reference (``*_xla``, the CPU training
path) and a Pallas kernel (``*_pallas``, interpreter only — on a TPU it
raises like the forward, ``lowering_conv.require_interpreter`` — tiled by
the same ``choose_tiles`` / ``vmem_bytes(pass_=...)`` model as the
forward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lowering_conv.lowering_conv import (choose_tiles,
                                                      require_interpreter)


# ---------------------------------------------------------------------------
# XLA reference paths
# ---------------------------------------------------------------------------

def wgrad_xla(lowered: jax.Array, dy: jax.Array, kshape) -> jax.Array:
    """lowered: (M, kh*kw*Cin) forward residual; dy: (..., Cout) cotangent.
    Returns dW (kh, kw, Cin, Cout) via one GEMM — no re-lowering."""
    kh, kw, cin, cout = kshape
    dy_flat = dy.reshape(-1, cout)
    return (lowered.T @ dy_flat).reshape(kh, kw, cin, cout)


def _col2im_accumulate(g, h: int, w: int, kh: int, kw: int,
                       stride: int) -> jax.Array:
    """The col2im core, shared by the XLA form and the Pallas dgrad
    kernel body: accumulate patch-column gradients g (B, Ho, Wo, kh*kw,
    Cin) onto a (B, H, W, Cin) grid via kh*kw interior-padded adds —
    dense and vectorizable, no scatter op."""
    b, ho, wo, _, cin = g.shape
    dx = jnp.zeros((b, h, w, cin), g.dtype)
    zero = jnp.zeros((), g.dtype)
    idx = 0
    for i in range(kh):
        for j in range(kw):
            cfg = ((0, 0, 0),
                   (i, h - (i + (ho - 1) * stride + 1), stride - 1),
                   (j, w - (j + (wo - 1) * stride + 1), stride - 1),
                   (0, 0, 0))
            dx = dx + jax.lax.pad(g[:, :, :, idx, :], zero, cfg)
            idx += 1
    return dx


def col2im_xla(dcols: jax.Array, x_shape, kh: int, kw: int,
               stride: int) -> jax.Array:
    """Scatter patch-column gradients (B*Ho*Wo, kh*kw*Cin) back onto the
    image grid (the lifting phase transposed)."""
    b, h, w, cin = x_shape
    ho = (h - kh) // stride + 1
    wo = (w - kw) // stride + 1
    g = dcols.reshape(b, ho, wo, kh * kw, cin)
    return _col2im_accumulate(g, h, w, kh, kw, stride)


def dgrad_xla(dy: jax.Array, w: jax.Array, x_shape,
              stride: int) -> jax.Array:
    """dX via one GEMM against the kernel matrix, then col2im."""
    kh, kw, cin, cout = w.shape
    dy_flat = dy.reshape(-1, cout)
    dcols = dy_flat @ w.reshape(kh * kw * cin, cout).T
    return col2im_xla(dcols, x_shape, kh, kw, stride)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _wgrad_kernel(low_ref, dy_ref, out_ref):
    """Accumulate lowered-tile^T @ dy-tile into the (K, Cout) output. The
    output block is the same for every grid step, so it stays VMEM-resident
    and the grid reduces into it (sequential grid, standard Pallas reduce
    pattern; holds in interpret mode too)."""
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bp, rb, wo, K = low_ref.shape
    low = low_ref[...].reshape(bp * rb * wo, K)
    dy = dy_ref[...].reshape(bp * rb * wo, -1)
    out_ref[...] += jax.lax.dot_general(
        low, dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def wgrad_pallas(lowered: jax.Array, dy: jax.Array, kshape, *, bp: int = 8,
                 rb: int = 8) -> jax.Array:
    """lowered: (B, Ho, Wo, kh*kw*Cin) forward residual (``return_lowered``
    layout); dy: (B, Ho, Wo, Cout). Returns dW (kh, kw, Cin, Cout)."""
    kh, kw, cin, cout = kshape
    b, ho, wo, K = lowered.shape
    bp, rb = choose_tiles(b, ho, bp, rb)
    grid = (b // bp, ho // rb)
    dw_flat = pl.pallas_call(
        _wgrad_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bp, rb, wo, K), lambda ib, ir: (ib, ir, 0, 0)),
            pl.BlockSpec((bp, rb, wo, cout), lambda ib, ir: (ib, ir, 0, 0)),
        ],
        out_specs=pl.BlockSpec((K, cout), lambda ib, ir: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, cout), lowered.dtype),
        interpret=require_interpreter(),
    )(lowered, dy)
    return dw_flat.reshape(kh, kw, cin, cout)


def _dgrad_kernel(dy_ref, kt_ref, dx_ref, *, kh, kw, stride, h, w):
    """One batch block: dcols = dy @ K_hat^T (GEMM), then the fused col2im
    scatter onto the (bp, H, W, Cin) image block — all rows of the block at
    once, so adjacent output-row tiles never race on overlapping pixels."""
    bp, ho, wo, cout = dy_ref.shape
    K = kt_ref.shape[1]
    cin = K // (kh * kw)
    dy = dy_ref[...].reshape(bp * ho * wo, cout)
    dcols = jnp.dot(dy, kt_ref[...],
                    preferred_element_type=jnp.float32)   # (M, K) GEMM
    g = dcols.reshape(bp, ho, wo, kh * kw, cin)
    dx = _col2im_accumulate(g.astype(jnp.float32), h, w, kh, kw, stride)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def dgrad_pallas(dy: jax.Array, w: jax.Array, x_shape, *, stride: int = 1,
                 bp: int = 8) -> jax.Array:
    """dy: (B, Ho, Wo, Cout); w: (kh, kw, Cin, Cout). Returns dX
    ``x_shape``. Grid over batch blocks only (see ``_dgrad_kernel``)."""
    b, h, wdim, cin = x_shape
    kh, kw, _, cout = w.shape
    ho, wo = dy.shape[1], dy.shape[2]
    bp, _ = choose_tiles(b, ho, bp, 1)
    kt = w.reshape(kh * kw * cin, cout).T            # (Cout, K)
    return pl.pallas_call(
        functools.partial(_dgrad_kernel, kh=kh, kw=kw, stride=stride,
                          h=h, w=wdim),
        grid=(b // bp,),
        in_specs=[
            pl.BlockSpec((bp, ho, wo, cout), lambda ib: (ib, 0, 0, 0)),
            pl.BlockSpec((cout, kh * kw * cin), lambda ib: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bp, h, wdim, cin), lambda ib: (ib, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x_shape, dy.dtype),
        interpret=require_interpreter(),
    )(dy, kt)
