"""From a configuration file to what the program runs: the program's
config object, and weights and inputs made on the device from the seed.

The weights are the benchmark's own (one jitted call per model, in the
type they are served or trained in), so the plain references can take
the same arrays without taking anything the program made.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent


def load_config(name: str) -> Dict:
    path = HERE / "configs" / f"{name}.json"
    with open(path) as f:
        cfg = json.load(f)
    if cfg.get("name") != name:
        raise ValueError(f"{path} names {cfg.get('name')!r}, not {name!r}")
    return cfg


def key_from_seed(seed: int):
    """A PRNG key from any non-negative seed up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


# ---------------------------------------------------------------------------
# CNN (models/cnn.py)
# ---------------------------------------------------------------------------

def cnn_program_config(cfg: Dict):
    from repro.models import cnn as C
    return C.CNNConfig(
        name=cfg["name"], image_size=cfg["image_size"],
        in_channels=cfg["in_channels"], num_classes=cfg["num_classes"],
        convs=tuple(C.ConvSpec(f, k, stride=s, pool=p)
                    for f, k, s, p in cfg["convs"]),
        fc_dims=tuple(cfg["fc_dims"]), source=cfg["source"])


def cnn_shapes(cfg: Dict):
    """{"conv": [{"w", "b"}], "fc": [{"w", "b"}]} leaf shapes: conv
    weights HWIO, fc weights (in, out)."""
    conv, fc = [], []
    size, c_in = cfg["image_size"], cfg["in_channels"]
    for f, k, s, p in cfg["convs"]:
        conv.append({"w": (k, k, c_in, f), "b": (f,)})
        o = (size - k) // s + 1
        size = o // p if p > 1 else o
        c_in = f
    dims = [size * size * c_in, *cfg["fc_dims"], cfg["num_classes"]]
    for j in range(len(dims) - 1):
        fc.append({"w": (dims[j], dims[j + 1]), "b": (dims[j + 1],)})
    return {"conv": conv, "fc": fc}


def cnn_weights(cfg: Dict, key):
    """Weights N(0, weight_gain^2 / fan_in), biases N(0, 0.01^2), float32,
    made in one jitted call (``weight_gain`` from the configuration)."""
    gain = float(cfg["weight_gain"])
    shapes = cnn_shapes(cfg)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda t: isinstance(t, tuple))

    def make(key):
        out = []
        for i, shp in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if len(shp) == 1:
                out.append(0.01 * jax.random.normal(k, shp, jnp.float32))
            else:
                fan_in = int(np.prod(shp[:-1]))
                out.append(jax.random.normal(k, shp, jnp.float32)
                           * np.float32(gain / np.sqrt(fan_in)))
        return tree.unflatten(out)

    return jax.block_until_ready(jax.jit(make)(key))


def image_pool(cfg: Dict, key, batches: int, batch: int, sharding=None
               ) -> List[Dict]:
    """``batches`` distinct batches {"images": (B, H, W, C) float32,
    "labels": (B,) int32}, made on the device in one jitted call."""
    hw, c, n = cfg["image_size"], cfg["in_channels"], cfg["num_classes"]

    def make(key):
        out = []
        for i in range(batches):
            ki, kl = jax.random.split(jax.random.fold_in(key, 1000 + i))
            out.append({"images": jax.random.normal(ki, (batch, hw, hw, c),
                                                    jnp.float32),
                        "labels": jax.random.randint(kl, (batch,), 0, n,
                                                     jnp.int32)})
        return out

    kw = {}
    if sharding is not None:
        kw["out_shardings"] = [{"images": sharding, "labels": sharding}
                               for _ in range(batches)]
    return jax.block_until_ready(jax.jit(make, **kw)(key))


# ---------------------------------------------------------------------------
# dense decoder LM (models/transformer.py)
# ---------------------------------------------------------------------------

def lm_program_config(cfg: Dict):
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


def lm_shapes(cfg: Dict):
    """The program's parameter tree for a dense decoder: blocks stacked
    on a leading layer axis. Each leaf is (shape, fan_in or "norm"/"embed")."""
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


def lm_weights(cfg: Dict, key):
    """Seeded weights in the served type, made on the device in one call:
    matrices N(0, 1/fan_in), the embedding N(0, initializer_range^2),
    norm offsets N(0, 0.1^2)."""
    return jax.block_until_ready(jax.jit(lm_weights_fn(cfg))(key))


def lm_weights_fn(cfg: Dict):
    """The function ``lm_weights`` jits: key -> weight tree."""
    dt = jnp.dtype(cfg["param_dtype"])
    spec = lm_shapes(cfg)
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
