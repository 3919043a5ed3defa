"""Execution-strategy plugins behind ``Engine.step`` / ``Engine.run``.

One registry, four deployable strategies plus the Theorem-1-exact
substrate the statistical-efficiency experiments need:

  sync           g=1 synchronous data-parallel SGD (the grouped step's
                 exact g=1 reduction; pinned to g=1)
  grouped-fused  g async compute groups, closed-form fused update
  grouped-scan   g async compute groups, literal O(g) sequential update
  trace-replay   execute momentum-SGD along a recorded EventTrace
                 (``repro.exec``) — run-level only, no per-round step
  delayed        exact delayed SGD (staleness S=g-1, paper Theorem 1) —
                 the Runner substrate for Algorithm 1 on CPU

A strategy provides ``build_step`` (a jittable per-round step +
host-side batch preparation) and/or ``run_stacked`` (a whole-run driver
over stacked batches, used by the Algorithm-1 Runner protocol).
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import numpy as np

from repro.core.async_sgd import delayed_sgd_run, make_grouped_train_step
from repro.core.compute_groups import group_batch_split
from repro.engine.spmd import (device_batch_split, make_reference_grouped_step,
                               make_spmd_grouped_step)

_REGISTRY: Dict[str, "Strategy"] = {}


def register_strategy(cls):
    inst = cls()
    _REGISTRY[inst.name] = inst
    return cls


def get_strategy(name: str) -> "Strategy":
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown strategy {name!r}; "
                         f"known: {sorted(_REGISTRY)}") from None


def list_strategies():
    return tuple(sorted(_REGISTRY))


class Strategy:
    """Interface. ``supports_step``: has a per-round ``step``;
    ``supports_runner``: usable as the Algorithm-1 Runner substrate."""
    name = "?"
    supports_step = True
    supports_runner = True

    def build_step(self, engine, *, g: int, lr: float, momentum: float,
                   per_group_batch: int, donate: bool):
        raise NotImplementedError(f"{self.name} has no per-round step")

    def run_stacked(self, engine, params, batches, *, g: int, lr: float,
                    momentum: float):
        raise NotImplementedError(f"{self.name} cannot drive a stacked run")


class _BuiltStep:
    """A compiled step + its batch-preparation recipe.

    spmd/reference bodies return per-shard (g, k) losses (their scalar
    mean is backend-fusion-dependent); ``__call__`` reduces them on the
    host in float64 so every mode reports one deterministic scalar.

    One compile serves both donating and non-donating callers: the step
    is jitted once (``donating`` records whether its params/momentum args
    are donated) and callers that do NOT own their buffers go through
    ``protected_call``, which copies them first when the compile donates
    — donation never enters the Engine's compile-cache key."""

    def __init__(self, fn: Callable, raw: Callable, prepare: Callable,
                 mode: str, g: int, k: int, donating: bool = False):
        self.fn = fn              # jitted (params, mom, device_batch)
        self.raw = raw            # un-jitted body (for lax.scan runs)
        self.prepare = prepare    # host: global batch -> device-form batch
        self.mode = mode          # "spmd" | "reference" | "vmap"
        self.g, self.k = g, k
        self.donating = donating  # fn donates its params/momentum args
        self.run_fn = None        # lazily-cached jitted whole-run scan

    @staticmethod
    def scalar_loss(loss):
        if getattr(loss, "ndim", 0) == 0:
            return loss
        return np.asarray(loss, np.float64).mean()

    def __call__(self, params, mom, batch):
        place = getattr(self.raw, "place", None)     # spmd: onto the mesh
        if place is not None:
            params, mom = place(params, mom)
        params, mom, loss = self.fn(params, mom, self.prepare(batch))
        return params, mom, self.scalar_loss(loss)

    def protected_call(self, params, mom, batch):
        """Call without consuming ``params``/``mom``: copies them first
        iff the shared compile donates (callers that own their buffers —
        ``Engine.run``'s loop — use ``__call__`` directly)."""
        if self.donating:
            params = jax.tree.map(jax.numpy.copy, params)
            mom = jax.tree.map(jax.numpy.copy, mom)
        return self(params, mom, batch)


class GroupedStrategy(Strategy):
    """g async compute groups; subclasses pick the update application."""
    update = "fused"

    def build_step(self, engine, *, g, lr, momentum, per_group_batch, donate):
        with engine.tracer.span("engine.build_step", strategy=self.name,
                                g=g) as sp:
            mode, k, mesh = engine._resolve_exec(g, per_group_batch)
            sp.set(mode=mode, k=k)
            weights = engine._weights_for(g)
            sizes = engine._sizes_for(g)
            common = dict(lr=lr, momentum=momentum,
                          weight_decay=engine.weight_decay,
                          strategy=self.update,
                          head_filter=engine.head_filter,
                          group_weights=weights,
                          update_impl=engine.update_impl)
            if mode == "spmd":
                raw = make_spmd_grouped_step(engine.loss_fn, mesh,
                                             bucket_bytes=engine.bucket_bytes,
                                             sharding_rules=engine.sharding_rules,
                                             **common)
            elif mode == "reference":
                raw = make_reference_grouped_step(engine.loss_fn, g, k,
                                                  **common)
            else:
                raw = make_grouped_train_step(engine.loss_fn, num_groups=g,
                                              **common)

            def prepare(batch):
                gb = group_batch_split(batch, g, sizes=sizes)
                if mode in ("spmd", "reference"):
                    gb = device_batch_split(gb, k)
                return gb

            fn = jax.jit(raw, donate_argnums=(0, 1) if donate else ())
        return _BuiltStep(fn, raw, prepare, mode, g, k, donating=donate)

    def run_stacked(self, engine, params, batches, *, g, lr, momentum):
        b = jax.tree.leaves(batches)[0].shape[1]
        per_group = engine._per_group_batch(g, b)
        # only step.raw / step.run_fn are used below (never the possibly
        # donating step.fn): Algorithm-1 probe runs re-enter with the same
        # parameter buffers, so the whole-run scan stays undonated
        step = engine._built_step(self, g=g, lr=lr, momentum=momentum,
                                  per_group_batch=per_group)
        dbatches = jax.vmap(step.prepare)(batches)
        mom = jax.tree.map(jax.numpy.zeros_like, params)

        # one jitted whole-run scan per built step: Algorithm-1 re-probes
        # the same (g, mu, eta) many times, and a fresh closure per call
        # would retrace the full T-step loop every probe
        run = step.run_fn
        if run is None:
            @jax.jit
            def run(p, v, db):
                def body(carry, bt):
                    p, v = carry
                    p, v, loss = step.raw(p, v, bt)
                    return (p, v), loss
                (p, v), losses = jax.lax.scan(body, (p, v), db)
                return p, v, losses
            step.run_fn = run

        final, _, losses = run(params, mom, dbatches)
        losses = np.asarray(losses)
        if losses.ndim > 1:                    # (T, g, k) per-shard losses
            losses = losses.mean(axis=tuple(range(1, losses.ndim)))
        return final, losses


@register_strategy
class GroupedFusedStrategy(GroupedStrategy):
    name = "grouped-fused"
    update = "fused"


@register_strategy
class GroupedScanStrategy(GroupedStrategy):
    name = "grouped-scan"
    update = "scan"


@register_strategy
class SyncStrategy(GroupedStrategy):
    """Synchronous data-parallel SGD = the grouped step at g=1 (the exact
    reduction ``core.async_sgd`` documents). Pinned to g=1: asking it for
    g>1 is a configuration error, not a silent strategy change."""
    name = "sync"
    update = "fused"

    def _check(self, g):
        if g != 1:
            raise ValueError(f"strategy 'sync' is pinned to g=1, got g={g}; "
                             "use grouped-fused/grouped-scan for g>1")

    def build_step(self, engine, *, g, lr, momentum, per_group_batch, donate):
        self._check(g)
        return super().build_step(engine, g=g, lr=lr, momentum=momentum,
                                  per_group_batch=per_group_batch,
                                  donate=donate)

    def run_stacked(self, engine, params, batches, *, g, lr, momentum):
        self._check(g)
        return super().run_stacked(engine, params, batches, g=g, lr=lr,
                                   momentum=momentum)


@register_strategy
class DelayedStrategy(Strategy):
    """Theorem-1-exact delayed SGD (gradient at W_{t-S}, S=g-1). Carries an
    (S+1)-deep parameter history — the CPU statistical-efficiency
    substrate, and the default Runner behind ``workload.make_runner``."""
    name = "delayed"
    supports_step = False

    def run_stacked(self, engine, params, batches, *, g, lr, momentum):
        final, losses, _ = delayed_sgd_run(
            engine.loss_fn, params, batches, staleness=g - 1, lr=lr,
            momentum=momentum, weight_decay=engine.weight_decay)
        return final, np.asarray(losses)


@register_strategy
class TraceReplayStrategy(Strategy):
    """Execute momentum-SGD along the engine's recorded ``EventTrace``
    (``repro.exec.replay``): one stale commit per trace event instead of
    round-robin rounds. Run-level only — per-commit staleness needs the
    whole schedule, so there is no per-round ``step`` and no Runner."""
    name = "trace-replay"
    supports_step = False
    supports_runner = False

    def replay(self, engine, params, batches, trace=None):
        """``trace`` (e.g. a truncated view) overrides ``engine.trace``."""
        from repro.exec import replay_trace   # lazy: keeps engine light
        trace = engine.trace if trace is None else trace
        if trace is None:
            raise ValueError("strategy 'trace-replay' needs Engine(trace=...)")
        return replay_trace(
            engine.loss_fn, params, batches, trace, lr=engine.lr,
            momentum=engine.momentum, weight_decay=engine.weight_decay,
            impl=engine.replay_impl, depth=engine.replay_depth)
