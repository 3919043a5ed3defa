"""Plain reference of a dense decoder LM configuration (the Phi-3/Phi-4
family as the configuration file states it): token embedding, per layer
RMSNorm -> grouped-query attention with RoPE -> residual -> RMSNorm ->
SwiGLU -> residual, final RMSNorm, head tied to the embedding.

It imports nothing of the program. It runs layer by layer over the
weights the benchmark made (one layer upcast at a time), in float32 at
``Precision.HIGHEST``, over whole sequences with a causal mask: no cache,
no paging, no batching of unrelated requests beyond padding at the end.

``quant="fp8"`` is the control: every matmul operand rounded to
float8_e4m3fn with one scale per tensor (its absolute maximum mapped to
448), accumulated in float32.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F8_MAX = 448.0


def _q(x, quant: Optional[str]):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant), precision=HI,
                      preferred_element_type=jnp.float32)


def _norm(x, s, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + s)


def _rope(x, theta):
    """Interleaved pairs (2i, 2i+1) over the whole head; x (B, T, H, hd)."""
    T, hd = x.shape[1], x.shape[-1]
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, hd, 2, dtype=jnp.float32)
                    / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs     # (T, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(blocks, i, x, *, cfg_items, quant):
    cfg = dict(cfg_items)
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), blocks)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, T, _ = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    a = _norm(x, p["ln1"], eps)
    q = _rope(_mm("btd,dhk->bthk", a, p["attn"]["wq"], quant), theta)
    k = _rope(_mm("btd,dhk->bthk", a, p["attn"]["wk"], quant), theta)
    v = _mm("btd,dhk->bthk", a, p["attn"]["wv"], quant)
    g = h // kv
    q = q.reshape(B, T, kv, g, hd)
    s = _mm("bqkgd,bskd->bkgqs", q, k, quant) / math.sqrt(hd)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgqs,bskd->bqkgd", w, v, quant).reshape(B, T, h, hd)
    x = x + _mm("bthk,hkd->btd", o, p["attn"]["wo"], quant)
    m = _norm(x, p["ln2"], eps)
    up = _mm("btd,df->btf", m, p["mlp"]["w_up"], quant)
    gate = _mm("btd,df->btf", m, p["mlp"]["w_gate"], quant)
    return x + _mm("btf,fd->btd", jax.nn.silu(gate) * up, p["mlp"]["w_down"],
                   quant)


def final_hidden(weights, tokens, cfg: Dict, quant: Optional[str] = None):
    """tokens (B, T) int32 -> normed final hidden states (B, T, d) float32."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    x = weights["embed"]["tok"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(weights["blocks"], i, x, cfg_items=items, quant=quant)
    return _norm(x, weights["ln_f"].astype(jnp.float32), cfg["rms_norm_eps"])


@partial(jax.jit, static_argnames=("quant",))
def logits_at(weights, hidden, quant: Optional[str] = None):
    """hidden (N, d) -> logits (N, V) float32 through the head."""
    head = (weights["embed"]["unembed"] if "unembed" in weights["embed"]
            else weights["embed"]["tok"].T).astype(jnp.float32)
    return _mm("nd,dv->nv", hidden, head, quant)
