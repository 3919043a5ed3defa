"""Jit'd public wrapper: in-kernel paged flash-decode.

Takes the serving decode shapes as they are — q ``(B, 1, H, hd)`` (one
rotated query token per slot), the per-layer page pools, the slot page
tables and the per-row positions — and returns ``(B, 1, H, hd)``, the
layout ``serving.decode`` feeds the output projection. The GQA grouping
(H = K * G, head index ``k * G + g``) matches ``layers._grouped_scores``
so the paged kernel is a drop-in for the gathered dense path.

Compiled on a TPU, interpreted elsewhere (``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.paged_attention import paged_attention_pallas


@functools.partial(jax.jit, static_argnames=("window",))
def paged_attention(q, k_pages, v_pages, table, pos, *, window=None):
    """q: (B, 1, H, hd); k_pages/v_pages: (P, page, K, hd) with H = K*G;
    table: (B, n_pages) int32 (page 0 = scratch); pos: (B,) int32 current
    absolute position per row (its K/V already written). ``window``
    enables ring semantics over the table's W = n_pages*page slots.
    Returns (B, 1, H, hd)."""
    b, sq, h, hd = q.shape
    if sq != 1:
        raise ValueError(f"paged decode takes one query token, got Sq={sq}")
    kh = k_pages.shape[2]
    if h % kh:
        raise ValueError(f"H={h} must be a multiple of K={kh}")
    qg = q.reshape(b, kh, h // kh, hd)           # head h = k*G + g, grouped
    out = paged_attention_pallas(qg, k_pages, v_pages, table, pos,
                                 window=window)
    return out.reshape(b, 1, h, hd)
