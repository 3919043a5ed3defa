"""From a configuration file to what the program runs: the configuration
itself, the seed's key, and weights made on the device by the family's
``weights_fn`` (``families/``).

The weights are the benchmark's own (one jitted call per model, in the
type they are served or trained in), so the plain references can take
the same arrays without taking anything the program made.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

import jax
import numpy as np

import families

#: where configuration files are looked up by name
CONFIGS = Path(__file__).resolve().parent / "configs"


def load_config(name: str) -> Dict:
    path = CONFIGS / f"{name}.json"
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


def weights(cfg: Dict, key):
    """The family's seeded weights, made on the device in one call."""
    return jax.block_until_ready(
        jax.jit(families.of(cfg).weights_fn(cfg))(key))
