"""DeepSeek-V2-style decoder (``models/mla.py``, ``models/moe.py``):
latent attention with a decoupled, YaRN-scaled rotary part in every
layer, ``first_k_dense_replace`` leading dense SwiGLU layers, then
routed layers with shared experts, an untied head.

Configuration keys are those of the published ``config.json``, where
``n_routed_experts`` counts the experts held on this chip, the first of
the ``router_experts`` the router scores
(the configuration file's ``deployment`` says how the layer is shared).
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp


def _refuse(cfg: Dict) -> None:
    """Settings of the family that the program does not run."""
    rs = cfg["rope_scaling"] or {}
    unsupported = {
        "q_lora_rank": cfg.get("q_lora_rank") is not None,
        "scoring_func": cfg["scoring_func"] != "softmax",
        "topk_method": cfg["topk_method"] != "greedy",
        "routed_scaling_factor": cfg["routed_scaling_factor"] != 1,
        "moe_layer_freq": cfg["moe_layer_freq"] != 1,
        "n_group": cfg["n_group"] != 1 or cfg["topk_group"] != 1,
        "rope_scaling": rs.get("type") != "yarn",
        "hidden_act": cfg["hidden_act"] != "silu",
        "attention_bias": cfg["attention_bias"],
        "tie_word_embeddings": cfg["tie_word_embeddings"],
    }
    bad = sorted(k for k, v in unsupported.items() if v)
    if bad:
        raise ValueError(f"the program does not run these settings: {bad}")


def program_config(cfg: Dict):
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnConfig
    _refuse(cfg)
    rs = cfg["rope_scaling"]
    return ArchConfig(
        name=cfg["name"], arch_type="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False, act="swiglu",
        moe=MoEConfig(num_experts=cfg["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      num_shared_experts=cfg["n_shared_experts"],
                      router_experts=cfg["router_experts"],
                      norm_topk_prob=cfg["norm_topk_prob"]),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        yarn=YarnConfig(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        first_k_dense=cfg["first_k_dense_replace"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"],
        source=cfg["source"])


def _attn_shapes(cfg: Dict, n: int):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return {"wq": ((n, d, h, nope + rope), d),
            "wkv_a": ((n, d, r + rope), d),
            "kv_norm": ((n, r), "norm"),
            "wkv_b": ((n, r, h, nope + v), r),
            "wo": ((n, h, v, d), h * v)}


def shapes(cfg: Dict):
    """The program's parameter tree: each stack of blocks on a leading
    layer axis. Each leaf is (shape, fan_in or "norm"/"embed"/"router")."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    nd = cfg["first_k_dense_replace"]
    nm = cfg["num_hidden_layers"] - nd
    e, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * fe
    moe = {"router": ((nm, d, cfg["router_experts"]), "router"),
           "w_gate": ((nm, e, d, fe), d), "w_up": ((nm, e, d, fe), d),
           "w_down": ((nm, e, fe, d), fe)}
    if fs:
        moe["shared"] = {"w_gate": ((nm, d, fs), d), "w_up": ((nm, d, fs), d),
                         "w_down": ((nm, fs, d), fs)}
    tree = {
        "embed": {"tok": ((v, d), "embed"), "unembed": ((d, v), d)},
        "ln_f": ((d,), "norm"),
        "blocks": {"ln1": ((nm, d), "norm"), "attn": _attn_shapes(cfg, nm),
                   "ln2": ((nm, d), "norm"), "moe": moe},
    }
    if nd:
        f = cfg["intermediate_size"]
        tree["dense_blocks"] = {
            "ln1": ((nd, d), "norm"), "attn": _attn_shapes(cfg, nd),
            "ln2": ((nd, d), "norm"),
            "mlp": {"w_up": ((nd, d, f), d), "w_down": ((nd, f, d), f),
                    "w_gate": ((nd, d, f), d)}}
    return tree


def _leaves(cfg: Dict):
    is_leaf = lambda t: isinstance(t, tuple) and isinstance(t[0], tuple)
    return jax.tree.flatten(shapes(cfg), is_leaf=is_leaf)


def weights_fn(cfg: Dict):
    """key -> weight tree in the served type: matrices N(0, 1/fan_in),
    the embedding N(0, initializer_range^2), norm offsets N(0, 0.1^2), all
    in bfloat16 but the router, N(0, 1/hidden) in float32 as the program
    holds it. Leaf i is drawn from ``fold_in(key, i)`` in the tree's
    flattened order."""
    dt = jnp.dtype(cfg["param_dtype"])
    leaves, tree = _leaves(cfg)

    def make(key):
        out = []
        for i, (shp, kind) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            ldt = jnp.float32 if kind == "router" else dt
            if kind == "norm":
                scale = 0.1
            elif kind == "embed":
                scale = cfg["initializer_range"]
            elif kind == "router":
                scale = cfg["hidden_size"] ** -0.5
            else:
                scale = float(kind) ** -0.5
            out.append(jax.random.normal(k, shp, ldt) * jnp.asarray(scale, ldt))
        return tree.unflatten(out)

    return make


def params(cfg: Dict) -> int:
    """Parameters held on this chip."""
    leaves, _ = _leaves(cfg)
    return sum(math.prod(shp) for shp, _ in leaves)


def _counts(cfg: Dict) -> Dict[str, int]:
    """Matmul parameters a token passes through, by part."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    L, nd = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    fe = cfg["moe_intermediate_size"]
    return {
        "attn": L * (d * h * (nope + rope) + d * (r + rope)
                     + r * h * (nope + v) + h * v * d),
        "dense": nd * 3 * d * cfg["intermediate_size"],
        "router": (L - nd) * d * cfg["router_experts"],
        "shared": (L - nd) * 3 * d * cfg["n_shared_experts"] * fe,
        "expert": 3 * d * fe,                 # one routed expert, one layer
        "head": d * cfg["vocab_size"],
    }


def _attention_flops(cfg: Dict, context: float) -> float:
    """Scores and value mixes of one token over ``context`` positions, all
    layers, in the decompressed form the prefill runs."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_hidden_layers"] * h * (qk + cfg["v_head_dim"]) * context


def forward_flops_per_token(cfg: Dict, context: float = 1020.0) -> float:
    """FLOPs of one token: 2 per matmul parameter it passes through (latent
    attention, the dense layer, routers, shared experts, the head, and
    ``num_experts_per_tok`` routed experts in expectation held here:
    top-k x held / router width), plus attention over ``context``
    positions (the chat median prompt: about a tenth of the total)."""
    c = _counts(cfg)
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / cfg["router_experts"]) * (cfg["num_hidden_layers"]
                                          - cfg["first_k_dense_replace"])
    matmul = (c["attn"] + c["dense"] + c["router"] + c["shared"] + c["head"]
              + routed * c["expert"])
    return 2.0 * matmul + _attention_flops(cfg, context)


def prefill_flops(cfg: Dict, prompt_lens: Sequence[int],
                  routed_pairs: int) -> float:
    """FLOPs a prefill of these prompts requires: each prompt token's
    matmuls (routed experts counted as the (token, held expert) pairs
    the program computed), and causal attention, token t over t + 1
    positions."""
    c = _counts(cfg)
    base = 2.0 * (c["attn"] + c["dense"] + c["router"] + c["shared"]
                  + c["head"])
    tokens = sum(prompt_lens)
    causal = sum(p * (p + 1) / 2 for p in prompt_lens)
    return (base * tokens + 2.0 * c["expert"] * routed_pairs
            + _attention_flops(cfg, 1.0) * causal)


def decode_bytes(cfg: Dict, live_tokens: int, experts_hit: int, *,
                 weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step must read: every weight once but the routed
    experts and the embedding table (the step looks up one row a slot),
    the ``experts_hit`` routed experts given a pair (summed over layers),
    and the latent row of each of the ``live_tokens`` positions the
    active slots read, in every layer. Routers are float32."""
    c = _counts(cfg)
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    norms = (2 * L + 1) * d + L * cfg["kv_lora_rank"]
    w = (c["attn"] + c["dense"] + c["shared"] + c["head"] + norms) * weight_bytes
    w += c["router"] * 4
    row = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * cache_bytes
    return w + experts_hit * c["expert"] * weight_bytes + live_tokens * L * row
