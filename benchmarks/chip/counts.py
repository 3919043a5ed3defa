"""Operations and bytes computed from a configuration's own shapes.

Every count here is a property of the configuration file, not of the
program: the benchmark reads sizes from ``configs/<name>.json`` and never
asks the program how much work it did.
"""
from __future__ import annotations

from typing import Dict, List


def cnn_layer_macs(cfg: Dict) -> List[Dict]:
    """Per layer of a CNN config: multiply-adds per image of the forward
    pass, with the geometry the configuration runs (VALID convs, square
    non-overlapping pools that drop the trailing rows)."""
    out = []
    size, c_in = cfg["image_size"], cfg["in_channels"]
    for i, (feat, k, stride, pool) in enumerate(cfg["convs"]):
        o = (size - k) // stride + 1
        out.append({"name": f"conv{i + 1}", "macs": o * o * k * k * c_in * feat,
                    "data_fed": i == 0})
        size = o // pool if pool > 1 else o
        c_in = feat
    dims = [size * size * c_in, *cfg["fc_dims"], cfg["num_classes"]]
    for j in range(len(dims) - 1):
        out.append({"name": f"fc{len(cfg['convs']) + j + 1}",
                    "macs": dims[j] * dims[j + 1], "data_fed": False})
    return out


def cnn_forward_flops(cfg: Dict) -> float:
    """FLOPs per image of the forward pass (2 per multiply-add)."""
    return 2.0 * sum(layer["macs"] for layer in cnn_layer_macs(cfg))


def cnn_train_flops(cfg: Dict) -> float:
    """FLOPs per image of forward + backward, no recompute: every layer's
    weight gradient costs its forward again, and every layer but the one
    fed by data computes its input gradient too."""
    total = 0.0
    for layer in cnn_layer_macs(cfg):
        passes = 2 if layer["data_fed"] else 3
        total += 2.0 * passes * layer["macs"]
    return total


def cnn_params(cfg: Dict) -> int:
    n = 0
    c_in = cfg["in_channels"]
    for feat, k, _, _ in cfg["convs"]:
        n += k * k * c_in * feat + feat
        c_in = feat
    dims = _fc_dims(cfg)
    for j in range(len(dims) - 1):
        n += dims[j] * dims[j + 1] + dims[j + 1]
    return n


def _fc_dims(cfg: Dict) -> List[int]:
    size, c_in = cfg["image_size"], cfg["in_channels"]
    for feat, k, stride, pool in cfg["convs"]:
        o = (size - k) // stride + 1
        size = o // pool if pool > 1 else o
        c_in = feat
    return [size * size * c_in, *cfg["fc_dims"], cfg["num_classes"]]


def lm_layer_params(cfg: Dict) -> int:
    """Parameters of one dense decoder layer (attention, SwiGLU, 2 norms)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    attn = d * h * hd * 2 + d * kv * hd * 2
    return attn + 3 * d * f + 2 * d


def lm_params(cfg: Dict) -> int:
    """All parameters of a dense decoder LM; a tied head adds none."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    emb = v * d * (1 if cfg["tie_word_embeddings"] else 2)
    return cfg["num_hidden_layers"] * lm_layer_params(cfg) + emb + d


def lm_matmul_params(cfg: Dict) -> int:
    """Parameters that take part in a matmul per token: every layer's
    projections and the output head (the embedding lookup is a gather)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (lm_layer_params(cfg) - 2 * d) + v * d


def lm_forward_flops_per_token(cfg: Dict) -> float:
    """2 FLOPs per matmul parameter per token (attention over the context
    is left out: under 3% of it below 1,024 positions at these widths)."""
    return 2.0 * lm_matmul_params(cfg)


def lm_decode_bytes(cfg: Dict, live_tokens: int, *, weight_bytes: int = 2,
                    kv_bytes: int = 2) -> float:
    """Bytes one decode step must read: every weight once (the tied
    embedding once, as the head) and the K and V rows of every live
    position in every layer."""
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // h
    kv_row = 2 * cfg["num_key_value_heads"] * hd * kv_bytes
    w = (cfg["num_hidden_layers"] * lm_layer_params(cfg)
         + cfg["vocab_size"] * d) * weight_bytes
    return w + cfg["num_hidden_layers"] * live_tokens * kv_row
