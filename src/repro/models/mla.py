"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1),
without query compression.

Per token x (D):

- ``q = x W_q``, per head ``[q_nope | q_pe]``;
- ``[c | k_pe] = x W_kv_a``, then ``c = RMSNorm(c)`` (the latent);
- ``[k_nope | v] = c W_kv_b``, per head;
- RoPE (YaRN-scaled where the config says) on ``q_pe`` per head and on
  ``k_pe`` once, shared by every head;
- scores ``q_nope·k_nope + q_pe·k_pe`` times ``softmax_scale(cfg)``; the
  output ``o_proj`` of the heads' value mixes.

A cache holds one row a token and layer: ``[c | k_pe]``, the latent after
its norm and the rotary key after its rotation (``latent_rows``).

Two forms of the same attention:

- ``mla_forward`` (prefill, training): decompressed — keys and values
  per head from every row, causal over the whole sequence; it returns
  the rows for a cache.
- ``mla_decode`` (decode): absorbed — ``W_UK`` folds into the query
  (``q_lat = W_UKᵀ q_nope``, so a score is ``q_lat·c + q_pe·k_pe``) and
  ``W_UV`` into the output (``W_UV Σ p c``), so a step reads only the
  cached rows, never per-head keys or values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L


def init_mla(key, cfg: ArchConfig):
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    r = m.kv_lora_rank
    dt = cfg.dtype("param")
    ks = jax.random.split(key, 4)
    return {
        "wq": (jax.random.normal(ks[0], (d, h, m.qk_head_dim))
               * d ** -0.5).astype(dt),
        "wkv_a": (jax.random.normal(ks[1], (d, m.latent_dim))
                  * d ** -0.5).astype(dt),
        "kv_norm": L.init_rms_norm(r, dt),
        "wkv_b": (jax.random.normal(
            ks[2], (r, h, m.qk_nope_head_dim + m.v_head_dim))
            * r ** -0.5).astype(dt),
        "wo": (jax.random.normal(ks[3], (h, m.v_head_dim, d))
               * (h * m.v_head_dim) ** -0.5).astype(dt),
    }


def softmax_scale(cfg: ArchConfig) -> float:
    """qk_head_dim^-0.5, times mscale(mscale_all_dim)^2 under YaRN."""
    s = cfg.mla.qk_head_dim ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        s *= L.yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return s


def _rope(x, positions, cfg: ArchConfig):
    return L.rope(x, positions, cfg.rope_theta, cfg.yarn)


def queries(p, x, positions, cfg: ArchConfig):
    """x (B,S,D) -> q_nope (B,S,H,nope), q_pe rotated (B,S,H,rope)."""
    cd = cfg.dtype("compute")
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    nope = cfg.mla.qk_nope_head_dim
    return q[..., :nope], _rope(q[..., nope:], positions, cfg)


def latent_rows(p, x, positions, cfg: ArchConfig):
    """x (B,S,D) -> the cache rows (B,S,latent_dim): ``[RMSNorm(c) |
    RoPE(k_pe)]``."""
    cd = cfg.dtype("compute")
    r = cfg.mla.kv_lora_rank
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(cd))
    c = L.rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kv[..., None, r:], positions, cfg)[..., 0, :]
    return jnp.concatenate([c, k_pe], axis=-1)


def mla_forward(p, x, cfg: ArchConfig, positions=None):
    """Decompressed causal attention over x (B,S,D). Returns (y (B,S,D),
    the rows (B,S,latent_dim) for a cache)."""
    cd = cfg.dtype("compute")
    m = cfg.mla
    r, nope = m.kv_lora_rank, m.qk_nope_head_dim
    S = x.shape[1]
    if positions is None:
        positions = jnp.arange(S)[None, :]
    with jax.named_scope("mla"):
        q_nope, q_pe = queries(p, x, positions, cfg)
        rows = latent_rows(p, x, positions, cfg)
        kv = jnp.einsum("bsr,rhk->bshk", rows[..., :r], p["wkv_b"].astype(cd))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        scores = (jnp.einsum("bqhn,bshn->bhqs", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhp,bsp->bhqs", q_pe, rows[..., r:],
                               preferred_element_type=jnp.float32))
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        scores = jnp.where(causal, scores * softmax_scale(cfg), -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(cd)
        out = jnp.einsum("bhqs,bshv->bqhv", w, v)
        y = jnp.einsum("bqhv,hvd->bqd", out, p["wo"].astype(cd))
    return y, rows


def mla_decode(p, x, rows, valid, pos, cfg: ArchConfig):
    """Absorbed attention of one new token per batch row over cached rows.

    x (B,1,D); rows (B,W,latent_dim), this token's own row among them;
    valid (B,W) bool: the rows it may read; pos (B,) its position.
    Returns y (B,1,D)."""
    cd = cfg.dtype("compute")
    m = cfg.mla
    r, nope = m.kv_lora_rank, m.qk_nope_head_dim
    with jax.named_scope("mla"):
        q_nope, q_pe = queries(p, x, pos[:, None], cfg)
        w_kv_b = p["wkv_b"].astype(cd)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_kv_b[..., :nope])
        q_cat = jnp.concatenate([q_lat, q_pe], axis=-1)      # (B,1,H,r+rope)
        scores = jnp.einsum("bshc,bwc->bhsw", q_cat, rows.astype(cd),
                            preferred_element_type=jnp.float32)
        scores = jnp.where(valid[:, None, None, :],
                           scores * softmax_scale(cfg), -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(cd)
        o_lat = jnp.einsum("bhsw,bwr->bshr", w, rows[..., :r].astype(cd))
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w_kv_b[..., nope:])
        return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(cd))
