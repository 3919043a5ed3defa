"""Plain reference of a DeepSeek-V2 configuration (the published
``modeling_deepseek.py`` as the configuration file states it): token
embedding; per layer RMSNorm -> multi-head latent attention -> residual
-> RMSNorm -> SwiGLU (the ``first_k_dense_replace`` leading layers) or
the expert layer -> residual; final RMSNorm; an untied head.

Attention is the decompressed form: ``q = x W_q`` split per head into
``[q_nope | q_pe]``; ``[c | k_pe] = x W_kv_a``, ``c`` normed; ``[k_nope |
v] = c W_kv_b`` per head; ``q_pe`` and the one shared ``k_pe`` rotated as
the published code does (de-interleave, then rotate halves) with YaRN's
frequencies; softmax over ``q·k`` times ``qk_head_dim^-0.5 * m^2``, ``m =
0.1 * mscale_all_dim * ln(factor) + 1``. The expert layer scores all
``router_experts`` experts in float32, takes a softmax and the greedy
top ``num_experts_per_tok``, renormalises only under ``norm_topk_prob``,
and adds each held expert's SwiGLU (the first ``n_routed_experts``,
every one evaluated on every token and weighted by its gate, 0 where it
was not chosen) and the shared experts'. What experts held elsewhere would add is left out, as
the configuration's deployment says.

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
import numpy as np
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


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn(cfg: Dict):
    """(inv_freq (dim/2,), cos/sin scale, softmax scale, (low, high)) as
    the published ``DeepseekV2YarnRotaryEmbedding`` and attention set
    them."""
    rs = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float64)
                                     / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low if high != low
                                                   else 0.001), 0.0, 1.0)
    mask = 1.0 - ramp
    inv_freq = inter * (1 - mask) + extra * mask
    cs = (_yarn_mscale(factor, rs["mscale"])
          / _yarn_mscale(factor, rs["mscale_all_dim"]))
    qk = cfg["qk_nope_head_dim"] + dim
    sm = qk ** -0.5
    if rs.get("mscale_all_dim"):
        sm *= _yarn_mscale(factor, rs["mscale_all_dim"]) ** 2
    return inv_freq.astype(np.float32), cs, sm, (low, high)


def _rope(x, inv_freq, cs):
    """x (B, T, H, d): pairs (2i, 2i+1) rotated by frequency i, in the
    published de-interleaved-halves order."""
    B, T, H, d = x.shape
    x = x.reshape(B, T, H, d // 2, 2).swapaxes(-1, -2).reshape(B, T, H, d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([ang, ang], -1)                   # (T, d)
    cos = (jnp.cos(emb) * cs)[None, :, None, :]
    sin = (jnp.sin(emb) * cs)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _attention(p, a, cfg, quant):
    B, T, _ = a.shape
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope = cfg["qk_nope_head_dim"]
    inv_freq, cs, scale, _ = yarn(cfg)
    q = _mm("btd,dhk->bthk", a, p["wq"], quant)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], inv_freq, cs)
    kv_a = _mm("btd,dr->btr", a, p["wkv_a"], quant)
    c = _norm(kv_a[..., :r], p["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _rope(kv_a[..., None, r:], inv_freq, cs)          # (B,T,1,rope)
    kv = _mm("btr,rhk->bthk", c, p["wkv_b"], quant)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    H = q.shape[2]
    qf = jnp.concatenate([q_nope, q_pe], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, T, H, rope))],
                         -1)
    s = _mm("bqhk,bshk->bhqs", qf, kf, quant) * scale
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bhqs,bshv->bqhv", w, v, quant)
    return _mm("bqhv,hvd->bqd", o, p["wo"], quant)


def _swiglu(x, w, quant):
    up = _mm("btd,df->btf", x, w["w_up"], quant)
    gate = _mm("btd,df->btf", x, w["w_gate"], quant)
    return _mm("btf,fd->btd", jax.nn.silu(gate) * up, w["w_down"], quant)


def _experts(p, m, cfg, quant):
    """The held experts' share of the expert layer, and the shared
    experts, over m (B, T, d)."""
    probs = jax.nn.softmax(_mm("btd,de->bte", m, p["router"], quant), -1)
    gates, idx = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    held = jnp.arange(cfg["n_routed_experts"])
    # each held expert's gate for each token, 0 where it was not chosen
    w = jnp.sum(jnp.where(idx[..., None] == held, gates[..., None], 0.0),
                axis=-2)                                     # (B, T, E)
    up = _mm("btd,edf->btef", m, p["w_up"], quant)
    gate = _mm("btd,edf->btef", m, p["w_gate"], quant)
    h = jax.nn.silu(gate) * up * w[..., None]
    y = _mm("btef,efd->btd", h, p["w_down"], quant)
    if "shared" in p:
        y = y + _swiglu(m, p["shared"], quant)
    return y


@partial(jax.jit, static_argnames=("cfg_items", "dense", "quant"))
def _layer(stack, i, x, *, cfg_items, dense, quant):
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)
    eps = cfg["rms_norm_eps"]
    x = x + _attention(p["attn"], _norm(x, p["ln1"], eps), cfg, quant)
    m = _norm(x, p["ln2"], eps)
    return x + (_swiglu(m, p["mlp"], quant) if dense
                else _experts(p["moe"], m, cfg, quant))


#: the configuration keys the layers read (static arguments of their jit)
_KEYS = {"rms_norm_eps", "kv_lora_rank", "qk_rope_head_dim",
         "qk_nope_head_dim", "v_head_dim", "rope_theta", "rope_scaling",
         "num_experts_per_tok", "norm_topk_prob", "n_routed_experts"}


def _items(cfg: Dict):
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in cfg.items() if k in _KEYS))


def final_hidden(weights, tokens, cfg: Dict, quant: Optional[str] = None):
    """tokens (B, T) int32 -> normed final hidden states (B, T, d) float32."""
    items = _items(cfg)
    x = weights["embed"]["tok"][tokens].astype(jnp.float32)
    nd = cfg["first_k_dense_replace"]
    for i in range(nd):
        x = _layer(weights["dense_blocks"], i, x, cfg_items=items,
                   dense=True, quant=quant)
    for i in range(cfg["num_hidden_layers"] - nd):
        x = _layer(weights["blocks"], i, x, cfg_items=items, dense=False,
                   quant=quant)
    return _norm(x, weights["ln_f"].astype(jnp.float32), cfg["rms_norm_eps"])


@partial(jax.jit, static_argnames=("quant",))
def logits_at(weights, hidden, quant: Optional[str] = None):
    """hidden (N, d) -> logits (N, V) float32 through the head."""
    return _mm("nd,dv->nv", hidden,
               weights["embed"]["unembed"].astype(jnp.float32), quant)
