"""The unified execution engine — one object behind every training loop.

``Engine`` owns what the four previous per-caller loops each re-implemented
(``launch/train.py``'s hand-rolled loop + ``_replay_main``, the ad-hoc
Runner closures behind Algorithm 1, the replay drivers, the experiment
scripts):

- mesh construction (``launch.mesh.make_group_mesh``) and the
  ("group", "data") SPMD grouped step (``engine.spmd``) when devices are
  available, with a bit-exact single-device reference and the legacy
  vmapped path as fallbacks;
- parameter/batch placement and buffer donation of the jitted step;
- host-side batch preparation (group split, sized heterogeneous shares,
  per-device shards) and prefetch;
- per-step observability: ``engine.timing.Telemetry`` is a facade over an
  ``obs.metrics.MetricRegistry`` (step_s / data_wait_s / h2d_s / loss
  series — the stream the cluster subsystem calibrates from and
  ``train.py --metrics-out`` sinks to JSONL), and every phase of a round
  (data wait, dispatch, block, checkpoint) runs inside an ``obs.spans``
  span — zero-cost no-ops unless a tracer is installed, Chrome-trace
  exportable when one is (docs/observability.md);
- checkpoint hooks;
- the Algorithm-1 ``Runner`` protocol: an Engine *is* a Runner —
  ``engine(state, g=..., mu=..., eta=..., steps=..., probe=...)``.

Execution strategies are plugins (``engine.strategies``): ``sync``,
``grouped-fused``, ``grouped-scan``, ``trace-replay`` (+ ``delayed``, the
Theorem-1-exact CPU substrate).
"""
from __future__ import annotations

from typing import Callable, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compute_groups import GroupSpec
from repro.data.pipeline import prefetch
from repro.engine import timing
from repro.engine.spmd import DEFAULT_BUCKET_BYTES, choose_data_parallel
from repro.engine.strategies import Strategy, get_strategy
from repro.obs import spans

_END = object()     # prefetch-exhausted sentinel (run's data-wait spans)


class Engine:
    """Unified mesh-sharded execution engine (see module docstring).

    ``loss_fn(params, batch) -> scalar`` is the only model contract — the
    engine is model-agnostic (transformer, CNN, MLP, LSTM share one loop).

    Execution placement (``exec_mode``):
      "auto"       SPMD mesh when >= g devices are visible, else the
                   legacy single-device vmapped step
      "spmd"       require the ("group", "data") mesh (error if the
                   device pool is too small)
      "reference"  the single-device bit-exact twin of the SPMD step
                   (lax.map over the same (g, k) shard structure)
      "vmap"       the legacy single-device path
                   (``core.async_sgd.make_grouped_train_step``)

    ``sample_batches(key, steps, batch_size)`` + ``batch_size`` enable the
    Runner protocol (Algorithm 1). ``trace`` + strategy "trace-replay"
    switch ``run`` to executing along the recorded event schedule.

    ``bucket_bytes`` sets the slab size target of the SPMD step's
    overlapped bucketed gradient exchange (``engine.spmd``; 0 restores
    the legacy whole-tree gather).

    ``mp`` adds a model-parallel axis to the SPMD mesh: each of the g
    groups spends mp devices per worker on parameter/optimizer-state
    shards (``sharding.rules.engine_param_specs``), so the device budget
    becomes g*k*mp. ``sharding_rules`` optionally overrides the derived
    PartitionSpecs with explicit ``(regex-path-window, spec)`` rules
    (first match wins). Results stay bitwise equal to ``mp=1`` and to
    the reference path (``engine.spmd`` module doc).

    ``tracer``: an ``obs.spans`` tracer recording the engine's phase
    spans (run / data_wait / dispatch / block_until_ready / checkpoint,
    plus per-bucket exchange annotations on the SPMD path). Defaults to
    the tracer installed via ``obs.spans.install()`` at construction
    time — a shared no-op when none is.
    """

    def __init__(self, loss_fn: Callable, *, strategy: str = "grouped-fused",
                 num_groups: int = 1, lr: float = 0.02, momentum: float = 0.0,
                 weight_decay: float = 0.0,
                 group_weights: Optional[Sequence[float]] = None,
                 micro_sizes: Optional[Sequence[int]] = None,
                 head_filter: Optional[Callable] = None,
                 update_impl: str = "xla",
                 exec_mode: str = "auto", num_devices: Optional[int] = None,
                 mp: int = 1, sharding_rules=None,
                 donate: bool = True,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 sample_batches: Optional[Callable] = None,
                 batch_size: Optional[int] = None, seed: int = 0,
                 trace=None, replay_impl: str = "scan",
                 replay_depth: Optional[int] = None,
                 checkpoint_dir: str = "", checkpoint_every: int = 0,
                 prefetch_depth: int = 2, telemetry_skip: int = 1,
                 tracer=None):
        if exec_mode not in ("auto", "spmd", "reference", "vmap"):
            raise ValueError(f"unknown exec_mode {exec_mode!r}")
        self.loss_fn = loss_fn
        self.strategy: Strategy = get_strategy(strategy)
        self.num_groups = int(num_groups)
        if self.strategy.name == "sync" and self.num_groups != 1:
            raise ValueError(f"strategy 'sync' is pinned to g=1, got "
                             f"g={self.num_groups}; use grouped-fused/"
                             "grouped-scan for g>1")
        self.lr, self.momentum, self.weight_decay = lr, momentum, weight_decay
        self.group_weights = (tuple(float(w) for w in group_weights)
                              if group_weights is not None else None)
        self.micro_sizes = (tuple(int(s) for s in micro_sizes)
                            if micro_sizes is not None else None)
        self.head_filter = head_filter
        self.update_impl = update_impl
        self.exec_mode, self.num_devices = exec_mode, num_devices
        self.mp = int(mp)
        if self.mp < 1:
            raise ValueError(f"mp must be >= 1, got {mp}")
        if self.mp > 1 and exec_mode == "vmap":
            raise ValueError("exec_mode='vmap' has no model-parallel path; "
                             "use exec_mode='spmd' (or 'auto') for mp > 1")
        self.sharding_rules = (tuple(sharding_rules)
                               if sharding_rules is not None else None)
        self.donate = donate
        self.bucket_bytes = int(bucket_bytes)
        self.sample_batches, self.batch_size = sample_batches, batch_size
        self.seed = seed
        self.trace = trace
        self.replay_impl, self.replay_depth = replay_impl, replay_depth
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.prefetch_depth = prefetch_depth
        self.telemetry = timing.Telemetry(skip=telemetry_skip)
        # span tracer: the one installed via obs.spans.install() unless
        # given explicitly; a NullTracer (shared no-op spans) by default
        self.tracer = tracer if tracer is not None else spans.current()
        self._steps: dict = {}

    # ------------------------------------------------------------------
    # configuration resolution
    # ------------------------------------------------------------------

    def _weights_for(self, g: int):
        if self.group_weights is not None and len(self.group_weights) == g:
            return self.group_weights
        return None

    def _sizes_for(self, g: int):
        if self.micro_sizes is not None and len(self.micro_sizes) == g:
            return self.micro_sizes
        return None

    def _per_group_batch(self, g: int, global_batch: int) -> int:
        sizes = self._sizes_for(g)
        if sizes is not None:
            return max(sizes)     # sized splits wrap-fill to max(sizes)
        if global_batch % g:
            raise ValueError(f"batch {global_batch} not divisible by g={g}")
        return global_batch // g

    def _resolve_exec(self, g: int, per_group_batch: int):
        """-> (mode, k, mesh or None) for one g. The device budget is
        g*mp workers wide: each of the g groups spends mp devices per
        worker on model-parallel shards, so k data-parallel slots per
        group come out of n // (g * mp)."""
        n = self.num_devices if self.num_devices is not None \
            else jax.device_count()
        mp = self.mp
        if self.exec_mode == "vmap":
            return "vmap", 1, None
        if self.exec_mode == "reference":
            # runs on ONE device; n (num_devices= or the visible pool) only
            # shapes the (g, k) shard structure being mirrored — stranding
            # is not a real-hardware concern here, so no warning. mp only
            # narrows k the same way it narrows the SPMD mesh (the
            # reference is the bitwise target of the mp-sharded step, so
            # the mirrored (g, k) must match).
            return ("reference",
                    choose_data_parallel(per_group_batch,
                                         max(1, n // (g * mp)), warn=False),
                    None)
        slots = n // (g * mp)
        k = choose_data_parallel(per_group_batch, slots) if slots >= 1 else 0
        if self.exec_mode == "auto" and mp == 1 and (n <= 1 or k < 1):
            return "vmap", 1, None
        if k < 1:
            raise ValueError(
                f"exec_mode={self.exec_mode!r} needs >= {g * mp} devices "
                f"for g={g}, mp={mp} (have {n})")
        if k < slots:
            self.telemetry.note(
                f"stranded devices: g={g} mp={mp} uses k={k} of {slots} "
                f"per-group device slots (per-group batch "
                f"{per_group_batch} has no larger divisor)")
        from repro.launch.mesh import make_group_mesh
        return "spmd", k, make_group_mesh(g, k, mp)

    def _built_step(self, strategy: Strategy, *, g: int, lr: float,
                    momentum: float, per_group_batch: int):
        # donate is deliberately NOT part of the key: the step is compiled
        # once (donating iff self.donate) and non-owning callers protect
        # their buffers via _BuiltStep.protected_call, so run()-then-step()
        # on the same config reuses the compile instead of re-jitting
        key = (strategy.name, g, lr, momentum, per_group_batch)
        step = self._steps.get(key)
        if step is None:
            step = strategy.build_step(self, g=g, lr=lr, momentum=momentum,
                                       per_group_batch=per_group_batch,
                                       donate=self.donate)
            self._steps[key] = step
        return step

    def group_spec(self, g: Optional[int] = None) -> GroupSpec:
        g = self.num_groups if g is None else g
        n = self.num_devices if self.num_devices is not None \
            else jax.device_count()
        return GroupSpec(num_groups=g, num_devices=max(g, (n // g) * g))

    def describe(self, g: Optional[int] = None,
                 per_group_batch: Optional[int] = None) -> str:
        g = self.num_groups if g is None else g
        spec = self.group_spec(g)
        mode, k, _ = self._resolve_exec(
            g, per_group_batch if per_group_batch is not None
            else max(1, spec.group_size))
        mesh_s = ""
        if mode == "spmd":
            mesh_s = (f"({g}x{k}x{self.mp} mesh)" if self.mp > 1
                      else f"({g}x{k} mesh)")
        return (f"engine[{self.strategy.name}] g={g} S={spec.staleness} "
                f"mu_implicit={spec.implicit_momentum:.3f} "
                f"exec={mode}" + mesh_s)

    # ------------------------------------------------------------------
    # per-round step
    # ------------------------------------------------------------------

    def step(self, params, mom, batch):
        """One timed round on the global ``batch`` (leaves (B, ...)).
        Returns ``(params, mom, loss)``; wall time lands in telemetry.

        Never consumes the caller's buffers: the caller owns them and may
        hold other references, so when the shared compiled step donates
        (``Engine(donate=True)``, ``run``'s optimization) this call copies
        params/momentum first (``protected_call``) instead of compiling a
        second non-donating executable."""
        if not self.strategy.supports_step:
            raise ValueError(
                f"strategy {self.strategy.name!r} has no per-round step; "
                "use Engine.run")
        b = jax.tree.leaves(batch)[0].shape[0]
        built = self._built_step(
            self.strategy, g=self.num_groups, lr=self.lr,
            momentum=self.momentum,
            per_group_batch=self._per_group_batch(self.num_groups, b))
        self._annotate_buckets(built, params)
        with self.tracer.span("engine.step", g=self.num_groups,
                              mode=built.mode):
            t0 = timing.monotonic()
            params, mom, loss = built.protected_call(params, mom, batch)
            jax.block_until_ready(loss)
            self.telemetry.record(step_s=timing.monotonic() - t0)
        return params, mom, loss

    def _annotate_buckets(self, built, params) -> None:
        """One-time per built step: emit an ``exchange.bucket`` instant
        per gradient slab of the overlapped SPMD exchange (bytes, leaf
        count, head-ness), so the trace shows the collective layout the
        compiled step executes. The layout is host-computable from the
        parameter tree — the collectives themselves run inside jit, where
        host spans cannot reach."""
        if not self.tracer.enabled or getattr(built, "buckets_annotated",
                                              False):
            return
        built.buckets_annotated = True
        if built.mode != "spmd" or self.bucket_bytes <= 0:
            return
        from repro.core.async_sgd import head_mask_tree
        from repro.engine.buckets import assign_buckets
        leaves, tree = jax.tree.flatten(params)
        mask = tree.flatten_up_to(head_mask_tree(params, self.head_filter))
        for i, b in enumerate(assign_buckets(leaves, mask,
                                             self.bucket_bytes)):
            self.tracer.instant("exchange.bucket", bucket=i,
                                bytes=b.nbytes, leaves=len(b.indices),
                                dtype=b.dtype, head=b.is_head)

    # ------------------------------------------------------------------
    # whole runs
    # ------------------------------------------------------------------

    def run(self, params, mom, batches: Iterable, *, steps: int,
            log_every: int = 0, log: Callable = print):
        """Drive ``steps`` rounds from a per-step batch iterator with
        prefetch, telemetry, and checkpoint hooks. For the trace-replay
        strategy the iterator supplies one microbatch per trace commit.

        Returns ``(params, mom, losses)`` (losses: Python floats).
        """
        if self.strategy.name == "trace-replay":
            return self._run_replay(params, mom, batches, steps=steps,
                                    log_every=log_every, log=log)
        if self.donate:
            # the loop's donated buffers must be the engine's own: copy the
            # caller's initial params/momentum once so the first step's
            # donation can't delete arrays the caller still holds
            params = jax.tree.map(jnp.copy, params)
            mom = jax.tree.map(jnp.copy, mom)
        tracer = self.tracer
        losses = []
        loss_series = self.telemetry.registry.series("loss")
        it = prefetch(iter(batches), depth=self.prefetch_depth,
                      tracer=tracer, metrics=self.telemetry.registry)
        with tracer.span("engine.run", strategy=self.strategy.name,
                         g=self.num_groups, steps=steps):
            t_prev = timing.monotonic()
            for i in range(steps):
                with tracer.span("engine.data_wait", step=i):
                    batch = next(it, _END)
                if batch is _END:
                    break
                t_ready = timing.monotonic()
                b = jax.tree.leaves(batch)[0].shape[0]
                built = self._built_step(
                    self.strategy, g=self.num_groups, lr=self.lr,
                    momentum=self.momentum,
                    per_group_batch=self._per_group_batch(self.num_groups,
                                                          b))
                self._annotate_buckets(built, params)
                with tracer.span("engine.step", step=i, mode=built.mode):
                    with tracer.span("engine.dispatch"):
                        params, mom, loss = built(params, mom, batch)
                    with tracer.span("engine.block_until_ready"):
                        # syncs: step wall ends here
                        losses.append(float(loss))
                t_done = timing.monotonic()
                self.telemetry.record(step_s=t_done - t_ready,
                                      data_s=t_ready - t_prev)
                loss_series.append(losses[-1], step=i)
                t_prev = t_done
                if log_every and i % log_every == 0:
                    log(f"step {i:5d} loss {losses[-1]:.4f} "
                        f"({(t_done - t_ready) * 1e3:.0f} ms/it)")
                self._maybe_checkpoint(i + 1, params, mom)
        return params, mom, losses

    def replay(self, params, batches, *, steps: Optional[int] = None):
        """Execute the engine's trace along already-stacked ``batches``
        (leaves (T, ...), one microbatch per commit). Returns
        ``(final_params, losses (T,) ndarray)``; wall time lands in
        telemetry. ``Engine.run`` wraps this for per-step iterators."""
        trace = self.trace
        if trace is None:
            raise ValueError("strategy 'trace-replay' needs Engine(trace=...)")
        if steps is not None:
            trace = trace.truncate(steps)
        if len(trace) == 0:
            raise ValueError("trace has no commits to replay "
                             f"(after truncation to {steps})")
        # staleness-depth stream: the per-commit read-to-commit distance
        # the replay executes — the asynchrony the trace view renders
        reg = self.telemetry.registry
        stale = reg.series("staleness")
        for t, s in enumerate(trace.staleness):
            stale.append(float(s), step=t)
        reg.gauge("replay_max_staleness").set(trace.max_staleness)
        reg.counter("replay_commits").inc(len(trace))
        with self.tracer.span("engine.replay", commits=len(trace),
                              impl=self.replay_impl,
                              num_groups=trace.num_groups):
            t0 = timing.monotonic()
            final, losses, _ = self.strategy.replay(self, params, batches,
                                                    trace=trace)
            self.telemetry.record(step_s=timing.monotonic() - t0)
        return final, np.asarray(losses)

    def _run_replay(self, params, mom, batches, *, steps, log_every, log):
        del mom     # replay owns its momentum state (zeros at trace start)
        if self.trace is None:
            raise ValueError("strategy 'trace-replay' needs Engine(trace=...)")
        T = min(steps, len(self.trace))
        if T == 0:
            raise ValueError("trace has no commits to replay "
                             f"(after truncation to {steps})")
        collected = []
        for i, batch in enumerate(batches):
            if i >= T:
                break
            collected.append(batch)
        if len(collected) < T:
            raise ValueError(f"trace has {T} commits but the batch stream "
                             f"ended after {len(collected)}")
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *collected)
        final, losses = self.replay(params, stacked, steps=T)
        dt = self.telemetry.step_s[-1]
        if log_every:
            for i in range(0, T, log_every):
                log(f"commit {i:5d} loss {float(losses[i]):.4f}")
            log(f"replayed {T} commits in {dt:.2f}s "
                f"({dt / T * 1e3:.0f} ms/commit, impl={self.replay_impl})")
        new_mom = jax.tree.map(jnp.zeros_like, params)
        return final, new_mom, [float(x) for x in losses]

    def _maybe_checkpoint(self, step_no: int, params, mom) -> None:
        if not self.checkpoint_dir or not self.checkpoint_every:
            return
        if step_no % self.checkpoint_every:
            return
        from repro.checkpoint import checkpointing as CK   # lazy
        with self.tracer.span("engine.checkpoint", step=step_no):
            CK.save(f"{self.checkpoint_dir}/ckpt_{step_no:07d}",
                    {"params": params, "mom": mom}, step=step_no)
        self.telemetry.registry.counter("checkpoints").inc()

    # ------------------------------------------------------------------
    # Algorithm-1 Runner protocol
    # ------------------------------------------------------------------

    def __call__(self, state, *, g: int, mu: float, eta: float, steps: int,
                 probe: bool) -> Tuple[object, np.ndarray]:
        """``Runner`` protocol (``core.auto_optimizer``): run ``steps`` at
        (g, mu, eta) from ``state = (params, step_counter)``. Probe runs
        restart from the same checkpoint and do not advance the stream key
        schedule (paper App E-C)."""
        if not self.strategy.supports_runner:
            raise ValueError(
                f"strategy {self.strategy.name!r} is not a Runner substrate")
        if self.sample_batches is None or self.batch_size is None:
            raise ValueError("the Runner protocol needs Engine("
                             "sample_batches=..., batch_size=...)")
        params, t0 = state
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 t0 + (1 if probe else 0))
        batches = self.sample_batches(key, steps, self.batch_size)
        final, losses = self.strategy.run_stacked(
            self, params, batches, g=g, lr=eta, momentum=mu)
        if probe:
            return state, losses
        return (final, t0 + steps), losses

    # ------------------------------------------------------------------
    # telemetry -> cluster calibration
    # ------------------------------------------------------------------

    def profile(self, params, mom, batch, *, warmup: int = 1,
                iters: int = 5) -> float:
        """Black-box examples/s of the engine's own jitted step (the
        cluster subsystem's ``profile_device`` contract): the probe never
        looks inside the step."""
        from repro.cluster.devices import profile_device   # lazy
        b = jax.tree.leaves(batch)[0].shape[0]
        built = self._built_step(
            self.strategy, g=self.num_groups, lr=self.lr,
            momentum=self.momentum,
            per_group_batch=self._per_group_batch(self.num_groups, b))
        # the probe re-calls the step with the SAME buffers, so it must go
        # through the copy-protected entry when the shared compile donates
        return profile_device(built.protected_call, (params, mom, batch),
                              batch_size=b, warmup=warmup, iters=iters)

    def profiled_spec(self, spec, params, mom, batch, **kw):
        """``DeviceSpec`` with its throughput measured from this engine."""
        import dataclasses as _dc
        return _dc.replace(spec,
                           throughput=self.profile(params, mom, batch, **kw))
