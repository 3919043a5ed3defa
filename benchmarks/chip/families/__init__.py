"""One module per model family, chosen by a configuration's ``family``.

A family supplies everything the harness needs that depends on the
model's architecture, so a new family joins the benchmark as new files:
``families/<family>.py`` beside its configuration and reference.

A serving family provides

- ``program_config(cfg)``: the program's config object for the model;
- ``weights_fn(cfg)``: a function from a PRNG key to the weight tree the
  program serves, in the served type (``models.weights`` jits it);
- ``params(cfg)``: the number of parameters;
- ``forward_flops_per_token(cfg)``: FLOPs of the matmuls one token takes
  through the model (the active ones, where a layer routes);
- ``decode_bytes(cfg, live_tokens)``: bytes one decode step must read.

A training family provides ``program_config``, ``weights_fn``, ``params``,
``loss(cfg)`` (the loss ``Engine`` trains and its head filter),
``inputs(cfg, key, batches, batch, sharding=None)`` (a pool of distinct
batches made on the device) and ``train_flops(cfg)`` (FLOPs per sample of
forward and backward, no recompute).

Every count is a property of the configuration file, never asked of the
program.
"""
from __future__ import annotations

import importlib
from types import ModuleType
from typing import Dict


def of(cfg: Dict) -> ModuleType:
    """The module of ``cfg["family"]``, found on this package's search
    path (``__path__``)."""
    return importlib.import_module(f"{__name__}.{cfg['family']}")
