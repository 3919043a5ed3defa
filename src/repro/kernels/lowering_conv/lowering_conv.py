"""Pallas TPU kernel: convolution by fused lowering + GEMM (paper §III
adapted to TPU; design notes in docs/lowering_conv.md).

The paper materializes the lowered matrix for the whole batch in DRAM and
issues one big BLAS GEMM — trading memory footprint for GEMM efficiency,
bounded by off-chip memory. On TPU the analogous boundary is VMEM: this
kernel *never* materializes the lowered matrix in HBM. Each grid step loads
a (b_p, H, W, Cin) image block into VMEM, builds the lowered patch matrix
(b_p*rb*Wo, kh*kw*Cin) in registers/VMEM, and feeds a single MXU GEMM
against the (kh*kw*Cin, Cout) kernel matrix.

The paper's b_p knob (images lowered per GEMM) is the batch-block dimension
of the BlockSpec; the rows-block rb tiles output rows so the GEMM M dim
stays VMEM-resident. ``vmem_bytes`` exposes the footprint model
(paper Fig. 4c: memory grows linearly in b_p).

The kernels run only in the Pallas interpreter: on a TPU backend they
raise (``require_interpreter``). Two things keep them from compiling
there. ``lax.dynamic_slice`` inside the kernel (the output-row window of
``_lower_block``) and the strided in-kernel slices have no Mosaic
lowering, and each grid step holds whole ``(b_p, H, W, Cin)`` images in
VMEM with ``Cin`` on the 128-wide lane axis (CaffeNet conv1: Cin = 3, so
one 227x227 image pads to ~27 MB of VMEM). The training path on the chip
is ``conv_impl="lowering"``, the same algorithm through XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap (>= 1). O(sqrt n) via divisor
    pairs instead of decrement-by-1 probing."""
    cap = max(1, min(cap, n))
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            if d <= cap:
                best = max(best, d)
            if n // d <= cap:
                best = max(best, n // d)
        d += 1
    return best


def require_interpreter() -> bool:
    """``interpret=True`` for the lowering-conv kernels, or a clear error
    on a TPU backend, where they do not compile (module docstring)."""
    if not kernels.interpret_mode():
        raise NotImplementedError(
            "lowering_conv Pallas kernels have no TPU lowering: in-kernel "
            "lax.dynamic_slice/strided slices do not lower to Mosaic, and "
            "whole (b_p, H, W, Cin) images with Cin on the lane axis "
            "overflow VMEM; use conv_impl='lowering' (the XLA custom-VJP "
            "path) on TPU")
    return True


def choose_tiles(b: int, ho: int, bp: int, rb: int) -> tuple:
    """Resolve requested (b_p, r_b) to the tile sizes the kernel will
    actually run: the largest divisors of the batch / output-rows not
    exceeding the request. Exposed so benchmarks can report the real
    tiling instead of the requested one."""
    return largest_divisor(b, bp), largest_divisor(ho, rb)


def _lower_block(d, *, kh, kw, stride, rb, wo, ir):
    """Lower one (bp, H, W, Cin) image block into the patch matrix for
    output-row tile ``ir``: (bp*rb*wo, kh*kw*Cin). Shared by the forward
    kernel and the wgrad kernel (docs/lowering_conv.md)."""
    bp, H, W, cin = d.shape
    rows_in = (rb - 1) * stride + kh
    d_rows = jax.lax.dynamic_slice(
        d, (0, ir * rb * stride, 0, 0), (bp, rows_in, W, cin))
    cols = []
    for i in range(kh):
        for j in range(kw):
            sl = jax.lax.slice(d_rows, (0, i, j, 0),
                               (bp, i + (rb - 1) * stride + 1,
                                j + (wo - 1) * stride + 1, cin),
                               (1, stride, stride, 1))
            cols.append(sl)                        # (bp, rb, wo, cin)
    low = jnp.stack(cols, axis=3)                  # (bp, rb, wo, kh*kw, cin)
    return low.reshape(bp * rb * wo, kh * kw * cin)


def _kernel(d_ref, k_ref, r_ref, *, kh, kw, stride, rb, wo):
    d_hat = _lower_block(d_ref[...], kh=kh, kw=kw, stride=stride, rb=rb,
                         wo=wo, ir=pl.program_id(1))
    r = jnp.dot(d_hat, k_ref[...],                 # MXU GEMM
                preferred_element_type=jnp.float32)
    bp = d_ref.shape[0]
    r_ref[...] = r.reshape(bp, rb, wo, -1).astype(r_ref.dtype)


def _kernel_with_lowered(d_ref, k_ref, r_ref, low_ref, *, kh, kw, stride, rb,
                         wo):
    d_hat = _lower_block(d_ref[...], kh=kh, kw=kw, stride=stride, rb=rb,
                         wo=wo, ir=pl.program_id(1))
    r = jnp.dot(d_hat, k_ref[...],
                preferred_element_type=jnp.float32)
    bp = d_ref.shape[0]
    r_ref[...] = r.reshape(bp, rb, wo, -1).astype(r_ref.dtype)
    low_ref[...] = d_hat.reshape(bp, rb, wo, kh * kw * d_ref.shape[3]) \
                        .astype(low_ref.dtype)


def lowering_conv_pallas(x: jax.Array, w: jax.Array, *, stride: int = 1,
                         bp: int = 8, rb: int = 8,
                         return_lowered: bool = False):
    """x: (B,H,W,Cin); w: (kh,kw,Cin,Cout); VALID padding.

    bp: images lowered per GEMM (paper's b_p); rb: output-row tile.
    With ``return_lowered`` also emits the lowered patch matrix
    (B, Ho, Wo, kh*kw*Cin) — the residual the custom-VJP backward reuses
    (the paper's trade-memory-for-GEMM move applied to backprop: one extra
    HBM tensor instead of re-lowering in the backward pass).
    """
    b, h, wdim, cin = x.shape
    kh, kw, _, cout = w.shape
    ho = (h - kh) // stride + 1
    wo = (wdim - kw) // stride + 1
    bp, rb = choose_tiles(b, ho, bp, rb)
    k_hat = w.reshape(kh * kw * cin, cout)

    grid = (b // bp, ho // rb)
    kern = _kernel_with_lowered if return_lowered else _kernel
    out_specs = pl.BlockSpec((bp, rb, wo, cout), lambda ib, ir: (ib, ir, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, ho, wo, cout), x.dtype)
    if return_lowered:
        out_specs = [out_specs,
                     pl.BlockSpec((bp, rb, wo, kh * kw * cin),
                                  lambda ib, ir: (ib, ir, 0, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, ho, wo, kh * kw * cin), x.dtype)]
    return pl.pallas_call(
        functools.partial(kern, kh=kh, kw=kw, stride=stride, rb=rb, wo=wo),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bp, h, wdim, cin), lambda ib, ir: (ib, 0, 0, 0)),
            pl.BlockSpec((kh * kw * cin, cout), lambda ib, ir: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=require_interpreter(),
    )(x, k_hat)


def vmem_bytes(*, bp: int, rb: int, h: int, w: int, cin: int, kh: int, kw: int,
               cout: int, stride: int = 1, itemsize: int = 4,
               pass_: str = "fwd") -> int:
    """VMEM working set of one grid step — the TPU version of the paper's
    Fig. 4(c) linear-in-b_p memory model, extended to the backward kernels.

    pass_:
      "fwd"    image block + lowered tile + kernel matrix + output tile
      "wgrad"  lowered-residual tile + dy tile + (K, Cout) accumulator
               (``bwd.wgrad_pallas``: consumes the forward's lowered
               residual, so no image block is resident)
      "dgrad"  dy block + kernel matrix + dcols tile + dx image block
               (``bwd.dgrad_pallas``: rb is ignored — the col2im scatter
               needs all output rows of a batch block at once)
    """
    ho = (h - kh) // stride + 1
    wo = (w - kw) // stride + 1
    K = kh * kw * cin
    if pass_ == "fwd":
        terms = (bp * h * w * cin,          # image block
                 bp * rb * wo * K,          # lowered tile (registers/VMEM)
                 K * cout,                  # kernel matrix
                 bp * rb * wo * cout)       # output tile
    elif pass_ == "wgrad":
        terms = (bp * rb * wo * K,          # lowered-residual tile
                 bp * rb * wo * cout,       # dy tile
                 K * cout)                  # wgrad accumulator
    elif pass_ == "dgrad":
        terms = (bp * ho * wo * cout,       # dy block (all rows)
                 K * cout,                  # kernel matrix
                 bp * ho * wo * K,          # dcols tile
                 bp * h * w * cin)          # dx image block
    else:
        raise ValueError(f"unknown pass_ {pass_!r} "
                         "(expected fwd | wgrad | dgrad)")
    return sum(terms) * itemsize
