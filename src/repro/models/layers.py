"""Core transformer layers: RMSNorm, RoPE, GQA attention (full / sliding /
cross / decode-with-cache), SwiGLU & GeLU MLPs.

Pure-functional: params are nested dicts of jnp arrays; every init_* has a
matching apply function. Attention defaults to a memory-efficient chunked
(flash-semantics) implementation in plain XLA; the Pallas kernel in
``repro.kernels.flash_attention`` is the TPU fast path (selected via
``attn_impl``).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, YarnConfig

# Threshold at/above which train/prefill attention switches to the chunked
# (flash-semantics) implementation to avoid materializing S^2 scores.
CHUNKED_ATTN_THRESHOLD = 4096
KV_CHUNK = 1024


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def init_rms_norm(d: int, dtype) -> jax.Array:
    return jnp.zeros((d,), dtype=dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_correction_range(yarn: YarnConfig, dim: int, theta: float):
    """(low, high): the rotary pair indices between which YaRN blends
    interpolated into extrapolated frequencies — those that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    def pair(turns):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair(yarn.beta_fast)), 0)
    high = min(math.ceil(pair(yarn.beta_slow)), dim - 1)
    return low, high


def rope(x: jax.Array, positions: jax.Array, theta: float,
         yarn: Optional[YarnConfig] = None) -> jax.Array:
    """Rotary embedding over the last axis, in interleaved pairs.
    x: (..., S, H, hd); positions: (..., S). With ``yarn``, DeepSeek-V2's
    YaRN frequencies: pairs below ``low`` keep theta's frequency, pairs
    above ``high`` take it divided by ``factor``, a linear ramp between;
    cos and sin scaled by mscale(mscale) / mscale(mscale_all_dim)."""
    if theta <= 0.0:
        return x
    hd = x.shape[-1]
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    scale = 1.0
    if yarn is not None:
        low, high = yarn_correction_range(yarn, hd, theta)
        ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        freqs = freqs / yarn.factor * ramp + freqs * (1.0 - ramp)
        scale = (yarn_mscale(yarn.factor, yarn.mscale)
                 / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                                 # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> jax.Array:
    """Whisper-style sinusoidal position embeddings (fp32)."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10_000.0) * dim / d)
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig, *, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    dt = cfg.dtype("param")
    std = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, h, hd)) * std).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, kv, hd)) * std).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, kv, hd)) * std).astype(dt),
        "wo": (jax.random.normal(ks[3], (h, hd, d)) * std).astype(dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h, hd), dtype=dt)
        p["bk"] = jnp.zeros((kv, hd), dtype=dt)
        p["bv"] = jnp.zeros((kv, hd), dtype=dt)
    return p


def _project_qkv(p, x, kv_src, cfg: ArchConfig):
    from repro.sharding.rules import maybe_replicate_for_decode
    cd = cfg.dtype("compute")
    x = maybe_replicate_for_decode(x)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    src = x if kv_src is None else kv_src
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(cd))
    if "bq" in p:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    return q, k, v


def _grouped_scores(q, k):
    """q (B,Sq,H,hd), k (B,Sk,K,hd) with H = K*G -> scores (B,K,G,Sq,Sk)."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, sq, kh, g, hd)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / math.sqrt(hd)


def _apply_scores(w, v):
    """w (B,K,G,Sq,Sk), v (B,Sk,K,hd) -> (B,Sq,H,hd)."""
    b, kh, g, sq, sk = w.shape
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(b, sq, kh * g, v.shape[-1])


def _mask_bias(sq, sk, q_offset, *, causal: bool, window: Optional[int]):
    """Additive mask bias (Sq,Sk) in fp32. q position i attends to k position j."""
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    ok = jnp.ones((sq, sk), dtype=bool)
    if causal:
        ok &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        ok &= kpos[None, :] > (qpos[:, None] - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def full_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                   q_offset: int = 0):
    """Reference O(S^2)-memory attention (grouped-query)."""
    scores = _grouped_scores(q, k).astype(jnp.float32)
    bias = _mask_bias(q.shape[1], k.shape[1], q_offset, causal=causal, window=window)
    w = jax.nn.softmax(scores + bias, axis=-1).astype(q.dtype)
    return _apply_scores(w, v)


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                      kv_chunk: int = KV_CHUNK):
    """Flash-semantics attention: lax.scan over KV chunks with running
    max/denominator. O(Sq * kv_chunk) live score memory."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kh = k.shape[2]
    g = h // kh
    if sk % kv_chunk != 0:
        return full_attention(q, k, v, causal=causal, window=window)
    nchunks = sk // kv_chunk
    qg = q.reshape(b, sq, kh, g, hd)
    kc = k.reshape(b, nchunks, kv_chunk, kh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunks, kv_chunk, kh, hd).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(sq)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        idx, kb, vb = inp
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb).astype(jnp.float32)
        scores = scores / math.sqrt(hd)
        kpos = idx * kv_chunk + jnp.arange(kv_chunk)
        ok = jnp.ones((sq, kv_chunk), dtype=bool)
        if causal:
            ok &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= kpos[None, :] > (qpos[:, None] - window)
        scores = scores + jnp.where(ok, 0.0, -1e30)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l_prev * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(q.dtype), vb)
        acc = acc * alpha[..., None].astype(q.dtype) + pv
        return (m_new, l_new, acc), None

    from repro.sharding.rules import constrain_batch
    m0 = constrain_batch(jnp.full((b, kh, g, sq), -jnp.inf, dtype=jnp.float32))
    l0 = constrain_batch(jnp.zeros((b, kh, g, sq), dtype=jnp.float32))
    acc0 = constrain_batch(jnp.zeros((b, kh, g, sq, hd), dtype=q.dtype))
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (jnp.arange(nchunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None].astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)


def attention_forward(p, x, cfg: ArchConfig, *, positions=None, causal=True,
                      window: Optional[int] = None, kv_src=None,
                      attn_impl: str = "xla"):
    """Train/prefill attention over a whole sequence. Returns (out, (k, v))
    so prefill can populate a cache."""
    from repro.sharding.rules import (constrain_batch, constrain_kv_seq,
                                      seq_parallel_enabled)
    cd = cfg.dtype("compute")
    q, k, v = _project_qkv(p, x, kv_src, cfg)
    q, k, v = constrain_batch(q), constrain_batch(k), constrain_batch(v)
    seq_par = seq_parallel_enabled() and kv_src is None
    if seq_par:
        # hillclimb variant: distribute attention over the tensor axis by
        # sharding K/V on sequence (heads needn't divide the axis). Q-side
        # sharding was tried and refuted — the backward pass re-gathers the
        # whole residual per layer (18.4 s vs 5.3 s; EXPERIMENTS.md §Perf).
        k, v = constrain_kv_seq(k), constrain_kv_seq(v)
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    if kv_src is None:  # self-attention gets RoPE
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    if attn_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=causal, window=window)
    elif (x.shape[1] >= CHUNKED_ATTN_THRESHOLD and kv_src is None
          and not seq_par):
        # chunked flash-semantics scan; under seq-parallel the KV-seq dim is
        # mesh-sharded and the scan reslicing fights GSPMD — use the direct
        # form whose scores stay sharded on Sk instead (§Perf)
        out = chunked_attention(q, k, v, causal=causal, window=window)
    else:
        out = full_attention(q, k, v, causal=causal, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd))
    return y, (k, v)


def attention_decode(p, x, cache, pos, cfg: ArchConfig, *,
                     window: Optional[int] = None, kv_src_cache=None):
    """Single-token decode. x: (B,1,D). cache: {"k","v"}: (B,W,K,hd) ring
    buffer (W = window or full seq). pos: scalar int32 absolute position.
    Returns (out, new_cache)."""
    cd = cfg.dtype("compute")
    if kv_src_cache is not None:
        # cross-attention: static cache, no update
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
        if "bq" in p:
            q = q + p["bq"].astype(cd)
        out = full_attention(q, kv_src_cache["k"], kv_src_cache["v"], causal=False)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd)), cache
    q, k, v = _project_qkv(p, x, None, cfg)
    posb = jnp.full((x.shape[0], 1), pos, dtype=jnp.int32)
    q = rope(q, posb, cfg.rope_theta)
    k = rope(k, posb, cfg.rope_theta)
    W = cache["k"].shape[1]
    slot = pos % W if window is not None else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    # validity: absolute position of ring slot s
    slots = jnp.arange(W)
    if window is not None:
        base = pos - (pos % W)
        abs_pos = jnp.where(slots <= (pos % W), base + slots, base - W + slots)
    else:
        abs_pos = slots
    valid = (abs_pos <= pos) & (abs_pos >= 0)
    if window is not None:
        valid &= abs_pos > (pos - window)
    scores = _grouped_scores(q, ck.astype(cd)).astype(jnp.float32)
    scores = scores + jnp.where(valid, 0.0, -1e30)[None, None, None, None, :]
    w = jax.nn.softmax(scores, axis=-1).astype(cd)
    out = _apply_scores(w, cv.astype(cd))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd))
    return y, {"k": ck, "v": cv}


def init_attn_cache(batch: int, cfg: ArchConfig, seq_len: int,
                    window: Optional[int] = None):
    W = min(window, seq_len) if window is not None else seq_len
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    dt = cfg.dtype("compute")
    return {"k": jnp.zeros((batch, W, kv, hd), dtype=dt),
            "v": jnp.zeros((batch, W, kv, hd), dtype=dt)}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    dt = cfg.dtype("param")
    ks = jax.random.split(key, 3)
    p = {"w_up": (jax.random.normal(ks[1], (d, f)) * d ** -0.5).astype(dt),
         "w_down": (jax.random.normal(ks[2], (f, d)) * f ** -0.5).astype(dt)}
    if cfg.act == "swiglu":
        p["w_gate"] = (jax.random.normal(ks[0], (d, f)) * d ** -0.5).astype(dt)
    return p


def mlp_forward(p, x, cfg: ArchConfig):
    from repro.sharding.rules import maybe_replicate_for_decode
    cd = cfg.dtype("compute")
    x = maybe_replicate_for_decode(x)
    up = x @ p["w_up"].astype(cd)
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"].astype(cd)) * up
    else:
        h = jax.nn.gelu(up)
    return h @ p["w_down"].astype(cd)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ArchConfig):
    dt = cfg.dtype("param")
    ks = jax.random.split(key, 2)
    p = {"tok": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(ks[1], (cfg.d_model, cfg.vocab_size))
                        * cfg.d_model ** -0.5).astype(dt)
    return p


def embed(p, tokens, cfg: ArchConfig):
    return p["tok"].astype(cfg.dtype("compute"))[tokens]


def unembed(p, x, cfg: ArchConfig):
    w = p["unembed"] if "unembed" in p else p["tok"].T
    return (x @ w.astype(cfg.dtype("compute"))).astype(jnp.float32)
