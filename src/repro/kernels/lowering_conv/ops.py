"""Jit'd public wrappers for the lowering conv — now fully trainable.

``lowering_conv`` (Pallas) and ``lowering_conv_xla`` (same algorithm
through XLA) carry a ``custom_vjp`` whose backward expresses both
gradients as batched GEMMs over the *same* lowered patch matrix the
forward built (``bwd.py``; design in docs/lowering_conv.md):

  wgrad = lowered(x)^T @ dy        reusing the forward's lowered residual
  dgrad = dy @ K_hat^T, col2im     one GEMM + the lifting phase transposed

``needs_dgrad=False`` skips the input gradient entirely (Caffe's
``propagate_down=false`` for data-fed layers): a custom_vjp is opaque to
JAX's dead-code elimination, so the first conv layer of a network must
say so explicitly — generic autodiff gets the same effect from DCE.

``lowering_conv_autodiff`` is the pre-custom-VJP formulation (generic XLA
autodiff through the lowering), kept as the baseline the throughput bench
compares against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lowering_conv import bwd
from repro.kernels.lowering_conv.lowering_conv import lowering_conv_pallas
from repro.kernels.lowering_conv.ref import lower, lowered_conv_ref


# ---------------------------------------------------------------------------
# XLA path (the training path, on the CPU and the TPU)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _lc_xla(x, w, stride, needs_dgrad, x_shape):
    return lowered_conv_ref(x, w, stride=stride)


def _lc_xla_fwd(x, w, stride, needs_dgrad, x_shape):
    b, h, _, cin = x.shape
    kh, kw, _, cout = w.shape
    ho = (h - kh) // stride + 1
    wo = (x.shape[2] - kw) // stride + 1
    d_hat = lower(x, kh, kw, stride)                 # lowering phase
    r = (d_hat @ w.reshape(kh * kw * cin, cout))     # one big GEMM
    return r.reshape(b, ho, wo, cout), (d_hat, w)    # d_hat is the residual


def _lc_xla_bwd(stride, needs_dgrad, x_shape, res, dy):
    d_hat, w = res
    dw = bwd.wgrad_xla(d_hat, dy, w.shape)
    if needs_dgrad:
        dx = bwd.dgrad_xla(dy, w, x_shape, stride)
    else:
        dx = jnp.zeros(x_shape, dy.dtype)
    return dx, dw


_lc_xla.defvjp(_lc_xla_fwd, _lc_xla_bwd)


def lowering_conv_xla_traced(x, w, *, stride: int = 1,
                             needs_dgrad: bool = True):
    """Un-jitted form for call sites already inside a jitted (and possibly
    vmapped) training step — a nested jit under the engine's group-vmap
    costs ~2x on CPU. Model code (``models.cnn``) uses this."""
    return _lc_xla(x, w, stride, needs_dgrad, tuple(x.shape))


@functools.partial(jax.jit, static_argnames=("stride", "needs_dgrad"))
def lowering_conv_xla(x, w, *, stride: int = 1, needs_dgrad: bool = True):
    """Convolution via lowering + one big GEMM through XLA (the paper's
    CPU plan with b_p = b), with the custom batched-GEMM backward."""
    return lowering_conv_xla_traced(x, w, stride=stride,
                                    needs_dgrad=needs_dgrad)


# ---------------------------------------------------------------------------
# Pallas path
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _lc_pallas(x, w, stride, bp, rb, needs_dgrad, x_shape):
    return lowering_conv_pallas(x, w, stride=stride, bp=bp, rb=rb)


def _lc_pallas_fwd(x, w, stride, bp, rb, needs_dgrad, x_shape):
    r, lowered = lowering_conv_pallas(x, w, stride=stride, bp=bp, rb=rb,
                                      return_lowered=True)
    return r, (lowered, w)


def _lc_pallas_bwd(stride, bp, rb, needs_dgrad, x_shape, res, dy):
    lowered, w = res
    dw = bwd.wgrad_pallas(lowered, dy, w.shape, bp=bp, rb=rb)
    if needs_dgrad:
        dx = bwd.dgrad_pallas(dy, w, x_shape, stride=stride, bp=bp)
    else:
        dx = jnp.zeros(x_shape, dy.dtype)
    return dx, dw.astype(w.dtype)


_lc_pallas.defvjp(_lc_pallas_fwd, _lc_pallas_bwd)


def lowering_conv_traced(x, w, *, stride: int = 1, bp: int = 8, rb: int = 8,
                         needs_dgrad: bool = True):
    """Un-jitted Pallas form (see ``lowering_conv_xla_traced``)."""
    return _lc_pallas(x, w, stride, bp, rb, needs_dgrad, tuple(x.shape))


@functools.partial(jax.jit, static_argnames=("stride", "bp", "rb",
                                             "needs_dgrad"))
def lowering_conv(x, w, *, stride: int = 1, bp: int = 8, rb: int = 8,
                  needs_dgrad: bool = True):
    """Convolution via fused lowering+GEMM (Pallas), trainable through the
    batched-GEMM backward kernels. The kernels run in the Pallas
    interpreter and raise on a TPU backend (``lowering_conv`` module doc).
    Tile sizes come from ``autotune.cached_tiles`` when the caller has
    probed them.
    """
    return lowering_conv_traced(x, w, stride=stride, bp=bp, rb=rb,
                                needs_dgrad=needs_dgrad)


# ---------------------------------------------------------------------------
# Generic-autodiff baseline
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("stride",))
def lowering_conv_autodiff(x, w, *, stride: int = 1):
    """The same lowering/GEMM algorithm differentiated by generic XLA
    autodiff — what ``lowering_conv_xla`` was before the custom VJP. The
    throughput bench's baseline (bench_cnn_throughput)."""
    return lowered_conv_ref(x, w, stride=stride)
