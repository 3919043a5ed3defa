"""Jit'd public wrapper: GQA-aware flash attention.

GQA no longer materializes repeated K/V before the kernel (the old
``jnp.repeat`` doubled/quadrupled the KV bytes for every GQA config):
K/V are flattened to one row per *kv* head and the kernel's BlockSpec
index maps stream each kv row to its ``H/K`` query-head rows
(``flash_attention_pallas(kv_group=...)``). Bitwise-identical to the
repeat formulation — same blocks, same dot order — pinned by
``tests/test_kernels.py::test_flash_attention_gqa_no_repeat_bitwise``.

``q_offsets`` (per-batch absolute query positions) is the decode hot
path's handle: a continuously-batched decode step has one query per
request at that request's own position, scored against the request's
gathered cache rows (``repro.serving.decode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    bq: int = 128, bk: int = 128, q_offsets=None):
    """q: (B, Sq, H, hd); k,v: (B, Sk, K, hd) with H = K*G (GQA: the
    query-head -> kv-head group map runs inside the kernel's flattened
    batch dimension; K/V are never repeated). ``q_offsets``: optional
    (B,) int32 absolute position of each batch row's first query (decode
    rows at per-request positions). Returns (B, Sq, H, hd).

    Compiled on a TPU, interpreted elsewhere
    (``repro.kernels.interpret_mode``).
    """
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    if h % kh:
        raise ValueError(f"H={h} must be a multiple of K={kh}")
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kh, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kh, v.shape[1], hd)
    offs = None
    if q_offsets is not None:
        offs = jnp.broadcast_to(
            q_offsets.astype(jnp.int32)[:, None], (b, h)).reshape(b * h)
    of = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                                bq=bq, bk=bk, kv_group=h // kh,
                                q_offsets=offs)
    return of.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
