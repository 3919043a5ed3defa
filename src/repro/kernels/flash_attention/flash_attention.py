"""Pallas TPU flash attention (causal / sliding-window).

Grid: (batch*heads, q_blocks, kv_blocks) with the kv dimension innermost and
sequential — running max / denominator / accumulator live in VMEM scratch
across kv steps (the classic flash recurrence, TPU-style: blocks sized for
VMEM, dots shaped for the 128x128 MXU).

GQA runs without materializing repeated K/V: when ``kv_group > 1`` the
query rows are laid out head-major (``b*H + h`` with ``h = kv_head *
kv_group + g``) while K/V keep one row per kv head (``b*K + kv_head``),
and the K/V BlockSpec *index maps* compute the kv row from the grid's
batch*head index — the same block arithmetic, one ``kv_group``-th of the
KV bytes streamed from HBM.

``q_offsets`` gives every batch*head row its own absolute query position
(the row's query index 0 sits at absolute position ``q_offsets[row]``) —
the decode hot path's contract, where a continuously-batched row decodes
one token at its own ``pos`` against a shared-capacity paged cache. With
offsets of 0 the masks reduce to the train/prefill causal forms bit-for-
bit (the offset is an integer add into the same comparison).

Sliding-window support doubles as the sub-quadratic path for the long_500k
input shape on dense architectures (``configs.base.INPUT_SHAPES``; the
window policy lives in ``launch.steps.effective_window``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq, bk, causal, window, scale, n_kv):
    b = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                    # (bq, hd)
    k = k_ref[0]                                    # (bk, hd)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    off = off_ref[b]                                # absolute pos of q row 0
    qpos = off + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), dtype=bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = (acc_scr[...] * alpha
                    + jnp.dot(p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == n_kv - 1)
    def _out():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window=None,
                           bq: int = 128, bk: int = 128, kv_group: int = 1,
                           q_offsets=None):
    """q: (BH, Sq, hd); k, v: (BH // kv_group, Sk, hd) — batch and heads
    pre-folded, q head-major so kv row = (bh // (K*G))*K + (bh % (K*G))//G.
    ``q_offsets``: optional (BH,) int32 absolute position of each row's
    first query (decode: the row's token position; default 0), read by
    scalar prefetch into SMEM before the grid starts."""
    bh, sq, hd = q.shape
    if kv_group < 1 or bh % kv_group:
        raise ValueError(f"kv_group={kv_group} must divide BH={bh}")
    if k.shape[0] != bh // kv_group or v.shape[0] != bh // kv_group:
        raise ValueError(f"k/v rows {k.shape[0]} != BH/kv_group "
                         f"{bh // kv_group}")
    sk = k.shape[1]
    bq = min(bq, sq)
    while sq % bq:
        bq -= 1
    bk = min(bk, sk)
    while sk % bk:
        bk -= 1
    n_kv = sk // bk
    grid = (bh, sq // bq, n_kv)
    scale = 1.0 / math.sqrt(hd)
    if q_offsets is None:
        q_offsets = jnp.zeros((bh,), jnp.int32)
    offs = q_offsets.astype(jnp.int32).reshape(bh)

    # head-major q rows: bh = batch*H + head with H = K*G, so the kv row
    # batch*K + head//G equals bh // G exactly (head < K*G) — the whole
    # GQA group map is one floor-divide in the K/V index maps, and with
    # kv_group == 1 it is the identity the pre-GQA wrapper compiled.
    def kv_row(b, _g=kv_group):
        return b // _g

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j, off: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, off: (kv_row(b), j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, off: (kv_row(b), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j, off: (b, i, 0)),
        scratch_shapes=[
            # running max / denom / accumulator, fp32 in VMEM
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, causal=causal, window=window,
                          scale=scale, n_kv=n_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        interpret=kernels.interpret_mode(),
    )(offs, q, k, v)
