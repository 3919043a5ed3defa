"""Dense decoder LM (``models/transformer.py``): GQA attention with RoPE,
a SwiGLU MLP, RMSNorm, optionally a tied head.

Configuration keys are those of a Hugging Face ``config.json``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def program_config(cfg: Dict):
    from repro.configs.base import ArchConfig
    if cfg.get("partial_rotary_factor", 1.0) != 1.0 or cfg.get("rope_scaling"):
        raise ValueError("the program runs full-dimension unscaled RoPE only")
    return ArchConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        qkv_bias=cfg["attention_bias"], act="swiglu",
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"],
        source=cfg["source"])


def shapes(cfg: Dict):
    """The program's parameter tree: blocks stacked on a leading layer
    axis. Each leaf is (shape, fan_in or "norm"/"embed")."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    embed = {"tok": ((v, d), "embed")}
    if not cfg["tie_word_embeddings"]:
        embed["unembed"] = ((d, v), d)
    return {
        "embed": embed,
        "ln_f": ((d,), "norm"),
        "blocks": {
            "ln1": ((L, d), "norm"),
            "attn": {"wq": ((L, d, h, hd), d), "wk": ((L, d, kv, hd), d),
                     "wv": ((L, d, kv, hd), d), "wo": ((L, h, hd, d), h * hd)},
            "ln2": ((L, d), "norm"),
            "mlp": {"w_up": ((L, d, f), d), "w_down": ((L, f, d), f),
                    "w_gate": ((L, d, f), d)},
        },
    }


def weights_fn(cfg: Dict):
    """key -> weight tree in the served type: matrices N(0, 1/fan_in),
    the embedding N(0, initializer_range^2), norm offsets N(0, 0.1^2).
    Leaf i is drawn from ``fold_in(key, i)`` in the tree's flattened
    order."""
    dt = jnp.dtype(cfg["param_dtype"])
    spec = shapes(cfg)
    is_leaf = lambda t: isinstance(t, tuple) and isinstance(t[0], tuple)
    leaves, tree = jax.tree.flatten(spec, is_leaf=is_leaf)

    def make(key):
        out = []
        for i, (shp, kind) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if kind == "norm":
                scale = 0.1
            elif kind == "embed":
                scale = cfg["initializer_range"]
            else:
                scale = float(kind) ** -0.5
            out.append((jax.random.normal(k, shp, dt) * jnp.asarray(scale, dt)))
        return tree.unflatten(out)

    return make


def layer_params(cfg: Dict) -> int:
    """Parameters of one decoder layer (attention, SwiGLU, 2 norms)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    attn = d * h * hd * 2 + d * kv * hd * 2
    return attn + 3 * d * f + 2 * d


def params(cfg: Dict) -> int:
    """All parameters; a tied head adds none."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    emb = v * d * (1 if cfg["tie_word_embeddings"] else 2)
    return cfg["num_hidden_layers"] * layer_params(cfg) + emb + d


def matmul_params(cfg: Dict) -> int:
    """Parameters that take part in a matmul per token: every layer's
    projections and the output head (the embedding lookup is a gather)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (layer_params(cfg) - 2 * d) + v * d


def forward_flops_per_token(cfg: Dict) -> float:
    """2 FLOPs per matmul parameter per token (attention over the context
    is left out: under 3% of it below 1,024 positions at these widths)."""
    return 2.0 * matmul_params(cfg)


def decode_bytes(cfg: Dict, live_tokens: int, *, weight_bytes: int = 2,
                 kv_bytes: int = 2) -> float:
    """Bytes one decode step must read: every weight once (the tied
    embedding once, as the head) and the K and V rows of every live
    position in every layer."""
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // h
    kv_row = 2 * cfg["num_key_value_heads"] * hd * kv_bytes
    w = (cfg["num_hidden_layers"] * layer_params(cfg)
         + cfg["vocab_size"] * d) * weight_bytes
    return w + cfg["num_hidden_layers"] * live_tokens * kv_row
