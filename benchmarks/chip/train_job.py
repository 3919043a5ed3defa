"""A training cell: a configuration trained through ``Engine`` on a pool
of batches made on the device from the seed.

Set-up builds one ``Engine`` and its state, and drives the first rounds
(``check_rounds`` of them, on distinct batches) through ``Engine.run``,
the window's own call and feed. Their losses, the momentum after round 1
and the parameter change after the last are what the plain reference is
compared with once the window has closed. The window then continues
from that same state, cycling the pool, for ``seconds``.
"""
from __future__ import annotations

import gc
import importlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import check
import families
import models
from window import Window


def _engine(cfg: Dict, traffic: Dict, chips: int, tracer=None):
    from repro.engine import Engine
    loss, head_filter = families.of(cfg).loss(cfg)
    return Engine(loss, strategy=traffic["strategy"],
                  num_groups=traffic["groups"],
                  lr=traffic["lr"], momentum=traffic["momentum"],
                  head_filter=head_filter,
                  update_impl=traffic["update_impl"],
                  exec_mode=traffic["exec_mode"], mp=traffic.get("mp", 1),
                  num_devices=chips, tracer=tracer)


def _pool_sharding(chips: int):
    if chips == 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:chips]), ("rows",))
    return NamedSharding(mesh, P("rows"))


def program_rounds(engine, p0, pool, rounds: int):
    """The first ``rounds`` rounds through ``Engine.run``: round 1 alone,
    so its momentum can be read, then the rest. Returns the state after
    them and what the comparison reads."""
    from repro.optim.sgd import init_momentum
    m0 = init_momentum(p0)
    p1, m1, losses = engine.run(p0, m0, iter(pool[:1]), steps=1)
    grad = check.leaf_norms(m1)
    p, m, more = engine.run(p1, m1, iter(pool[1:rounds]), steps=rounds - 1)
    losses = list(losses) + list(more)
    change = check.change_norms(p, p0)
    return p, m, {"losses": losses, "grad": grad, "change": change}


def reference_rounds(cfg: Dict, traffic: Dict, p0, pool, rounds: int,
                     dtype=jnp.float32, keep_rows=None) -> Dict:
    ref = importlib.import_module(f"configs.{cfg['reference']}")
    losses, mom1, p = ref.run_rounds(
        p0, pool[:rounds], cfg, groups=traffic["groups"], lr=traffic["lr"],
        momentum=traffic["momentum"], dtype=dtype, keep_rows=keep_rows)
    return {"losses": losses, "grad": check.leaf_norms(mom1),
            "change": check.change_norms(p, p0)}


def setup(cfg: Dict, traffic: Dict, seed: int, chips: int, tracer=None,
          engine=None):
    key = models.key_from_seed(seed)
    p0 = models.weights(cfg, key)
    if chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(_pool_sharding(chips).mesh, P())
        p0 = jax.device_put(p0, rep)
    pool = families.of(cfg).inputs(cfg, key, traffic["pool_batches"],
                                   traffic["global_batch"],
                                   sharding=_pool_sharding(chips))
    engine = engine or _engine(cfg, traffic, chips, tracer)
    rounds = traffic["check_rounds"]
    p, m, prog = program_rounds(engine, p0, pool, rounds)
    return {"engine": engine, "p0": p0, "pool": pool, "p": p, "m": m,
            "prog": prog}


def run(cell: Dict, cfg: Dict, traffic: Dict, seed: int, seconds: float,
        win: Window) -> Dict:
    chips = cell["chips"]
    st = setup(cfg, traffic, seed, chips, tracer=win.tracer)
    engine, pool = st["engine"], st["pool"]
    B = traffic["global_batch"]
    tel = engine.telemetry
    first = len(tel.step_s)

    def feed(deadline):
        i = 0
        while win.clock() < deadline:
            yield pool[i % len(pool)]
            i += 1

    win.setup_done()
    with win:
        p, m, losses = engine.run(st["p"], st["m"], feed(win.t0 + seconds),
                                  steps=1 << 40)
    steps = len(losses)
    out = {
        "attempted": steps,
        "failed": int(np.sum(~np.isfinite(np.asarray(losses, np.float64)))),
        "e2e": {"train_samples_per_s": steps * B / win.seconds},
        "layer": {"steps": steps, "batch": B, "chips": chips,
                  "data_wait_s": list(tel.data_s[first:]),
                  "step_s": list(tel.step_s[first:]),
                  "samples_per_s": steps * B / win.seconds},
        "info": {"window_loss_first": losses[0], "window_loss_last":
                 losses[-1], "window_loss_max": float(np.max(losses)),
                 "check_losses": st["prog"]["losses"]},
    }
    win.read_memory()
    del p, m, engine, st["p"], st["m"], st["engine"]
    pool = st["pool"][:traffic["check_rounds"]]
    p0, prog = st["p0"], st["prog"]
    del st
    gc.collect()
    ref = reference_rounds(cfg, traffic, p0, pool, traffic["check_rounds"])
    out["readings"] = check.train_readings(prog, ref)
    return out
