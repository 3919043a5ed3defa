"""Image CNN (``models/cnn.py``): VALID convolutions with optional
non-overlapping max pools, then fully connected layers, trained through
``Engine`` with the merged FC head.

A configuration's ``convs`` are [features, kernel, stride, pool window]
(pool 1 = none); ``fc_dims`` are the hidden FC widths.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def program_config(cfg: Dict):
    from repro.models import cnn as C
    return C.CNNConfig(
        name=cfg["name"], image_size=cfg["image_size"],
        in_channels=cfg["in_channels"], num_classes=cfg["num_classes"],
        convs=tuple(C.ConvSpec(f, k, stride=s, pool=p)
                    for f, k, s, p in cfg["convs"]),
        fc_dims=tuple(cfg["fc_dims"]), source=cfg["source"])


def loss(cfg: Dict):
    """(loss(params, batch), head_filter) for ``Engine``. The program's
    ``loss_fn`` is looked up at each call."""
    from repro.models import cnn as C
    pcfg = program_config(cfg)
    return (lambda p, b: C.loss_fn(p, b, pcfg)), C.head_filter


def shapes(cfg: Dict):
    """{"conv": [{"w", "b"}], "fc": [{"w", "b"}]} leaf shapes: conv
    weights HWIO, fc weights (in, out)."""
    conv, c_in = [], cfg["in_channels"]
    for f, k, _, _ in cfg["convs"]:
        conv.append({"w": (k, k, c_in, f), "b": (f,)})
        c_in = f
    dims = _fc_dims(cfg)
    fc = [{"w": (dims[j], dims[j + 1]), "b": (dims[j + 1],)}
          for j in range(len(dims) - 1)]
    return {"conv": conv, "fc": fc}


def weights_fn(cfg: Dict):
    """key -> weights N(0, weight_gain^2 / fan_in), biases N(0, 0.01^2),
    float32 (``weight_gain`` from the configuration). Leaf i is drawn
    from ``fold_in(key, i)`` in the tree's flattened order."""
    gain = float(cfg["weight_gain"])
    leaves, tree = jax.tree.flatten(shapes(cfg),
                                    is_leaf=lambda t: isinstance(t, tuple))

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

    return make


def inputs(cfg: Dict, key, batches: int, batch: int, sharding=None
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


def layer_macs(cfg: Dict) -> List[Dict]:
    """Per layer: multiply-adds per image of the forward pass, with the
    geometry the configuration runs (VALID convs, square non-overlapping
    pools that drop the trailing rows)."""
    out = []
    size, c_in = cfg["image_size"], cfg["in_channels"]
    for i, (feat, k, stride, pool) in enumerate(cfg["convs"]):
        o = (size - k) // stride + 1
        out.append({"name": f"conv{i + 1}", "macs": o * o * k * k * c_in * feat,
                    "data_fed": i == 0})
        size = o // pool if pool > 1 else o
        c_in = feat
    dims = _fc_dims(cfg)
    for j in range(len(dims) - 1):
        out.append({"name": f"fc{len(cfg['convs']) + j + 1}",
                    "macs": dims[j] * dims[j + 1], "data_fed": False})
    return out


def forward_flops(cfg: Dict) -> float:
    """FLOPs per image of the forward pass (2 per multiply-add)."""
    return 2.0 * sum(layer["macs"] for layer in layer_macs(cfg))


def train_flops(cfg: Dict) -> float:
    """FLOPs per image of forward + backward, no recompute: every layer's
    weight gradient costs its forward again, and every layer but the one
    fed by data computes its input gradient too."""
    total = 0.0
    for layer in layer_macs(cfg):
        passes = 2 if layer["data_fed"] else 3
        total += 2.0 * passes * layer["macs"]
    return total


def params(cfg: Dict) -> int:
    return sum(int(np.prod(shp)) for shp in jax.tree.leaves(
        shapes(cfg), is_leaf=lambda t: isinstance(t, tuple)))


def _fc_dims(cfg: Dict) -> List[int]:
    size, c_in = cfg["image_size"], cfg["in_channels"]
    for feat, k, stride, pool in cfg["convs"]:
        o = (size - k) // stride + 1
        size = o // pool if pool > 1 else o
        c_in = feat
    return [size * size * c_in, *cfg["fc_dims"], cfg["num_classes"]]
