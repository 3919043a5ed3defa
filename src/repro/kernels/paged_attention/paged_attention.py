"""Pallas TPU paged flash-decode: the page table is walked *inside* the
kernel, so decode bandwidth scales with each request's live context, not
the pool's worst-case capacity.

The serving decode hot path used to gather every slot's pages into a
dense ``(B, W, K, hd)`` ring copy per layer per step — ``W`` bytes moved
whether the request had 9 live tokens or 900. Here the K/V BlockSpec
*index maps* read the page table (a scalar-prefetch operand, resident in
SMEM before the grid starts) to pick the physical page block for each
grid step: pages are consumed in place, zero dense materialization.

Grid: ``(B, n_pages)`` — batch rows, then the slot's page list innermost
and sequential (the flash running max / denominator / accumulator live in
VMEM scratch across page steps). Three properties do the roofline work:

- **Length-bounded walk.** Per-row ``pos`` (also scalar-prefetched)
  bounds the live page count ``jmax``; tail steps clamp their index map
  to the last live page — an unchanged block index means the pipeline
  skips the HBM fetch — and ``pl.when`` skips their compute entirely.
  Work scales with ``pos[b]``, not ``W = n_pages * page``.
- **Repeat-free GQA.** Each grid step fetches one physical page for all
  K kv heads — a ``(page, K, hd)`` block whose trailing ``(K, hd)`` dims
  are the pool's own, which is the tiling the TPU compiler accepts — and
  a static loop over the kv heads scores each head's ``G = H/K`` query
  rows against its own K/V rows. K/V bytes stream once per page, never
  repeated per query head (the same no-``jnp.repeat`` contract as
  ``flash_attention``'s ``kv_row`` trick).
- **Ring-aware masking.** With ``window`` set the cache is a ring:
  slot ``s`` holds absolute position ``base + s`` or ``base - W + s``
  depending on which side of the write head it sits (the reference
  ``serving.decode._valid_mask`` per block). Once a row wraps
  (``pos >= W``) every page is live and the walk covers the table; the
  mask, not slot order, carries position — which is what let the old
  gathered-copy flash path reject sliding windows.

Masked/scratch-backed entries cannot leak into the value reduction: a
fully-masked page contributes ``p = exp(-inf - m) = 0`` rows once any
valid page has raised the running max, and every decode row has at least
its own just-written token valid (slot ``pos``), which the page walk
always visits.
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


def _live_jmax(pos, *, page: int, n_pages: int, ring: bool):
    """Index of the last live page for a row at position ``pos`` (the
    current token's page for linear caches; the whole table once a ring
    row wraps). Clamped so stale positions of retired slots can never
    index past the table row."""
    jmax = pos // page
    if ring:
        jmax = jnp.where(pos >= n_pages * page, n_pages - 1, jmax)
    return jnp.minimum(jmax, n_pages - 1)


def _kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, page, n_pages, scale, window, W):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    jmax = _live_jmax(pos, page=page, n_pages=n_pages,
                      ring=window is not None)

    @pl.when(j <= jmax)
    def _flash_step():
        slot = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        if window is None:
            ok = slot <= pos
        else:
            # ring validity: the reference _valid_mask, one page at a time
            base = pos - pos % W
            absp = jnp.where(slot <= pos % W, base + slot, base - W + slot)
            ok = (absp <= pos) & (absp >= 0) & (absp > pos - window)
        for h in range(q_ref.shape[1]):              # static loop, kv heads
            q = q_ref[0, h]                          # (G, hd)
            k = k_ref[0, :, h, :]                    # (page, hd)
            v = v_ref[0, :, h, :]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_scr[h]
            l_prev = l_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = (acc_scr[h] * alpha
                          + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32))
            m_scr[h] = m_new

    @pl.when(j == n_pages - 1)
    def _out():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, table, pos, *,
                           window=None):
    """q: (B, K, G, hd) one query token per row, grouped by kv head;
    k_pages/v_pages: (P, page, K, hd) physical pools; table: (B, n_pages)
    int32 page ids; pos: (B,) int32 absolute position of each row's
    current token (already written into its page). Returns (B, K, G, hd).
    """
    B, K, G, hd = q.shape
    _, page, Kp, hdp = k_pages.shape
    if (Kp, hdp) != (K, hd):
        raise ValueError(f"pool heads/dims {(Kp, hdp)} != query {(K, hd)}")
    n_pages = table.shape[1]
    W = n_pages * page
    if window is not None and W > window:
        raise ValueError(f"ring of {n_pages}x{page} slots exceeds "
                         f"window={window}")
    scale = 1.0 / math.sqrt(hd)
    ring = window is not None

    def kv_map(b, j, tbl, pos_s):
        # THE table walk: clamp dead-tail steps to the last live page so
        # their block index repeats (no new fetch), then map the logical
        # page j to its physical page id.
        jj = jnp.minimum(j, _live_jmax(pos_s[b], page=page,
                                       n_pages=n_pages, ring=ring))
        return (tbl[b, jj], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, K, G, hd), lambda b, j, tbl, pos_s: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, K, hd), kv_map),
            pl.BlockSpec((1, page, K, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd),
                               lambda b, j, tbl, pos_s: (b, 0, 0, 0)),
        scratch_shapes=[
            # per-kv-head running max / denom / accumulator, fp32 in VMEM
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page=page, n_pages=n_pages, scale=scale,
                          window=window, W=W),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=kernels.interpret_mode(),
    )(table.astype(jnp.int32), pos.astype(jnp.int32), q, k_pages, v_pages)
