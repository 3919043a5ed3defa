"""Continuous-batching serving loop over the paged KV cache.

The serving counterpart of ``engine.Engine``: one compiled decode step at
a fixed batch width (= slots) serves a changing request population —
requests are admitted into free slots as they arrive (queue), prefilled,
decoded one token per step, and retired the step their generation
completes, returning their pages to the pool. No call ever retraces on
population change: slot membership is data (page tables, position
vector, active mask), not shape.

Arrivals are an ``exec.trace.EventTrace`` (it is exactly an
arrival/commit log): ``commit_time`` carries arrival times and
``read_version[t] = t`` (staleness 0 — nothing is read asynchronously).
``poisson_trace`` draws reproducible Poisson arrivals; any saved trace
replays the same offered load.

Time is the repo's one clock (``engine.timing.monotonic``). The loop
runs on measured wall-clock, with one virtualization: when every slot is
empty and the next arrival is in the future, the clock skips forward
instead of sleeping, so a 50-request trace benches in compute time while
queueing delays stay real. Per-request output is independent of batch
composition (pinned in tests), so admission timing never changes tokens.

Prefill modes:
- ``"scan"`` (default): a jitted scan of the paged decode step over
  prompt positions, bucketed by prompt length — bitwise-identical cache
  and first token to the sequential reference (``T.prefill`` is the same
  scan over a dense cache).
- ``"parallel"``: one ``T.forward`` pass over the whole prompt
  (``attn_impl="pallas"`` routes it through the flash kernel), KV rows
  scattered into the slot's pages. One call instead of P steps — the
  prefill hot path — numerically allclose to scan, not bitwise
  (parallel vs stepwise attention reduction order). Full-window caches
  only: a ring-wrapped scatter would need last-writer selection. Under
  latent attention it writes each token's latent row instead of K/V.

Decode cost tracks live context, not pool capacity:
- ``attn_impl="pallas"`` routes decode (and the scan-prefill inner
  step) through the in-kernel paged-attention walk
  (``repro.kernels.paged_attention``) — no dense gather at all, per-row
  positions bound the page walk, sliding windows included.
- the XLA path gathers only up to the batch's live high-water page
  count, bucketed to a power-of-two page ladder (``gather_mode=
  "bucket"``) so changing populations reuse compiled steps;
  ``gather_mode="full"`` pins the full-capacity gather — the bitwise
  baseline arm.
- ``attn_impl="pallas_gather"`` (the legacy flash-over-a-copy hot path)
  cannot represent a wrapped ring: under a sliding window it falls back
  to the XLA path, and the server says so — ``warnings.warn`` +
  ``registry.note`` — instead of silently switching.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.engine.timing import monotonic
from repro.exec.trace import EventTrace
from repro.models import transformer as T
from repro.obs import spans
from repro.obs.metrics import MetricRegistry
from repro.serving.decode import ATTN_IMPLS, paged_decode_step
from repro.serving.paged_cache import PagedCacheSpec, PageAllocator, init_pages


# ---------------------------------------------------------------------------
# Offered load: traces and request sampling
# ---------------------------------------------------------------------------

def poisson_trace(rate: float, n: int, seed: int = 0) -> EventTrace:
    """Reproducible Poisson arrivals at ``rate`` req/s as an EventTrace
    (commit_time = arrival times, staleness 0)."""
    if rate <= 0:
        raise ValueError("rate must be positive")
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    t = np.arange(n, dtype=np.int64)
    return EventTrace(num_groups=1, group=np.zeros(n, np.int32),
                      read_version=t, commit_time=arrivals)


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request: prompt tokens + generation budget."""
    rid: int
    arrival: float
    prompt: np.ndarray          # (P,) int32
    gen: int


def sample_requests(trace: EventTrace, cfg: ArchConfig, *,
                    prompt_range=(8, 32), gen_range=(4, 32),
                    seed: int = 0) -> List[Request]:
    """One request per trace event. Prompt tokens and lengths come from an
    RNG keyed by (seed, rid) alone, so request rid is byte-identical across
    traces/rates — the solo bit-match tests and the continuous-vs-static
    bench replay the exact same work."""
    out = []
    for rid, arrival in enumerate(np.asarray(trace.commit_time)):
        rng = np.random.default_rng((seed, rid))
        plen = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
        gen = int(rng.integers(gen_range[0], gen_range[1] + 1))
        prompt = rng.integers(cfg.vocab_size, size=plen).astype(np.int32)
        out.append(Request(rid=rid, arrival=float(arrival),
                           prompt=prompt, gen=gen))
    return out


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeReport:
    """Per-request accounting for one serving run (times in seconds on the
    run's virtual clock; latency = finish - arrival). ``token_times[rid]``
    holds, per output token, the clock reading taken when the call that
    produced it (prefill or decode step) had synced: its first entry is
    the prefill's end, its last the request's finish."""
    mode: str
    rids: np.ndarray
    arrivals: np.ndarray
    queue_waits: np.ndarray
    latencies: np.ndarray
    gen_counts: np.ndarray
    tokens: Dict[int, np.ndarray]
    token_times: Dict[int, np.ndarray]
    makespan: float
    occupancy_mean: float

    def percentile(self, q: float) -> float:
        return float(np.percentile(self.latencies, q))

    @property
    def total_tokens(self) -> int:
        return int(self.gen_counts.sum())

    @property
    def throughput(self) -> float:
        """Generated tokens per second of makespan."""
        return self.total_tokens / max(self.makespan, 1e-12)

    def goodput(self, slo_s: float) -> float:
        """Tokens/s counting only requests whose latency met the SLO —
        the paper's HE x SE product transposed to serving: raw throughput
        discounted by the fraction of it that was statistically useful
        (delivered within the latency target)."""
        ok = self.latencies <= slo_s
        return float(self.gen_counts[ok].sum()) / max(self.makespan, 1e-12)


def _bucket(n: int, cap: Optional[int] = None) -> int:
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap) if cap is not None else b


# ---------------------------------------------------------------------------
# Continuous-batching server
# ---------------------------------------------------------------------------

class ContinuousServer:
    """Slot-recycled continuous batching (module docstring)."""

    def __init__(self, cfg: ArchConfig, params=None, *, slots: int = 8,
                 page_size: int = 16, max_seq: int = 256,
                 window: Optional[int] = "config", attn_impl: str = "xla",
                 prefill_mode: str = "scan", gather_mode: str = "bucket",
                 seed: int = 0,
                 registry: Optional[MetricRegistry] = None,
                 extra_pages: int = 0):
        if window == "config":
            window = cfg.sliding_window
        if prefill_mode not in ("scan", "parallel"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if prefill_mode == "parallel" and window is not None:
            raise ValueError("parallel prefill needs a full (non-ring) cache")
        if attn_impl not in ATTN_IMPLS:
            raise ValueError(f"attn_impl must be one of {ATTN_IMPLS}, "
                             f"not {attn_impl!r}")
        if gather_mode not in ("bucket", "full"):
            raise ValueError(f"unknown gather_mode {gather_mode!r}")
        self.cfg = cfg
        self.window = window
        self.attn_impl = attn_impl
        self.prefill_mode = prefill_mode
        self.gather_mode = gather_mode
        self.params = params if params is not None else T.init_params(
            jax.random.PRNGKey(seed), cfg)
        self.spec = PagedCacheSpec.for_config(
            cfg, num_slots=slots, page_size=page_size, max_seq=max_seq,
            window=window, extra_pages=extra_pages)
        self.alloc = PageAllocator(self.spec)
        self.pages = init_pages(self.spec)
        self.registry = registry if registry is not None else MetricRegistry()

        # the one remaining impl fallback, made loud: flash-over-a-copy
        # cannot express a wrapped ring, so sliding windows run the XLA
        # masked path — warn once and pin it in the metric stream's notes
        self._fallback_note: Optional[str] = None
        if attn_impl == "pallas_gather" and window is not None:
            self._fallback_note = (
                "attn_impl='pallas_gather' cannot run a sliding-window "
                f"(window={window}) ring cache: slot order != position "
                "order after wrap breaks the flash kernel's positional "
                "mask; decode falls back to the masked XLA path "
                "(attn_impl='pallas' walks the page table in-kernel and "
                "has no such fallback)")
            warnings.warn(self._fallback_note, stacklevel=2)
            self.registry.note(self._fallback_note)

        S = self.spec.num_slots
        win, impl = self.window, self.attn_impl
        # a routed stack's programs also return their expert counts, which
        # the host fetches with the tokens at the step's one sync
        self._moe = cfg.moe is not None

        def _step(params, pages, table, tokens, pos, active, *,
                  gather_pages: Optional[int] = None):
            stats = {}
            logits, pages = paged_decode_step(
                params, pages, table, tokens, pos, active, cfg,
                window=win, attn_impl=impl, gather_pages=gather_pages,
                stats=stats)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            if stats:
                tok = (tok, jnp.stack([stats["experts_hit"],
                                       stats["pairs"]]))
            return tok, pages

        self._step_impl = _step
        self._step_cache: Dict[Optional[int], Callable] = {}
        self._prefill_cache: Dict[tuple, Callable] = {}

        def _scan_prefill(params, pages, table, prompts, plens, admit, *,
                          gather_pages: Optional[int] = None):
            Pb = prompts.shape[1]

            def body(pg, t):
                tok = jax.lax.dynamic_slice_in_dim(prompts, t, 1, axis=1)
                act = admit & (t < plens)
                stats = {}
                logits, pg = paged_decode_step(
                    params, pg, table, tok, jnp.full((S,), t, jnp.int32),
                    act, cfg, window=win, attn_impl=impl,
                    gather_pages=gather_pages, stats=stats)
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return pg, (tok, stats.get("pairs"))

            pages, (toks, pairs) = jax.lax.scan(
                body, pages, jnp.arange(Pb, dtype=jnp.int32))
            if self._moe:                            # toks: (Pb, S)
                return pages, (toks, pairs.sum())
            return pages, toks

        def _parallel_prefill(params, pages, table, prompts, plens, admit, *,
                              gather_pages: Optional[int] = None):
            B, Pb = prompts.shape
            page = self.spec.page_size
            tpos = jnp.arange(Pb)[None, :]                     # (1, Pb)
            act = admit[:, None] & (tpos < plens[:, None])     # (B, Pb)
            batch = {"tokens": prompts}
            if self._moe:
                batch["mask"] = act        # route the prompt tokens only
            logits, _, cache = T.forward(params, batch, cfg,
                                         return_cache=True, attn_impl=impl,
                                         window=win)
            pidx = jnp.broadcast_to(tpos // page, (B, Pb))
            pid = jnp.take_along_axis(table, pidx, axis=1)     # (B, Pb)
            inpg = jnp.broadcast_to(tpos % page, (B, Pb))
            new_pages = {}
            for name, pool in pages.items():     # (L,P,pg,K,hd) / (L,P,pg,C)
                rows = cache["blocks"][name].astype(pool.dtype)
                actx = act[(None, slice(None), slice(None))
                           + (None,) * (pool.ndim - 3)]
                old = pool[:, pid, inpg]                       # (L,B,Pb,...)
                new_pages[name] = pool.at[:, pid, inpg].set(
                    jnp.where(actx, rows, old))
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32).T  # (Pb, B)
            if self._moe:
                toks = (toks, cache["moe_pairs"].sum())
            return new_pages, toks

        self._prefill_impl = (_scan_prefill if prefill_mode == "scan"
                              else _parallel_prefill)

    def reset(self, registry: Optional[MetricRegistry] = None) -> None:
        """Fresh pool/allocator (and optionally a fresh metric registry)
        while keeping every compiled step/prefill bucket — so a measured
        run can follow a warmup run without paying compilation twice."""
        self.alloc = PageAllocator(self.spec)
        self.pages = init_pages(self.spec)
        if registry is not None:
            self.registry = registry
            if self._fallback_note is not None:
                self.registry.note(self._fallback_note)

    def _uses_gather(self) -> bool:
        """Does the decode step materialize a dense gathered view at all?
        ``"pallas"`` walks the table in-kernel; everything else gathers."""
        return self.attn_impl != "pallas"

    def _step_fn(self, gather_pages: Optional[int]) -> Callable:
        """Compiled decode step for one static gather width (None = full
        capacity — the bitwise baseline). One entry per ladder rung."""
        fn = self._step_cache.get(gather_pages)
        if fn is None:
            fn = jax.jit(spans.named(
                functools.partial(self._step_impl, gather_pages=gather_pages),
                "serve_decode_step"), donate_argnums=(1,))
            self._step_cache[gather_pages] = fn
        return fn

    def _gather_bucket(self, slot_pos: np.ndarray,
                       active: np.ndarray) -> Optional[int]:
        """The batch's live high-water page count, rounded up the
        power-of-two ladder. Active rows only: retired slots keep stale
        positions that must not widen (or overrun) the gather. None means
        full width — pallas (no gather), ``gather_mode="full"``, or a
        batch already at capacity."""
        if self.gather_mode == "full" or not self._uses_gather():
            return None
        if not active.any():
            return None
        live = min(int(slot_pos[active].max()) + 1, self.spec.seq_capacity)
        gp = _bucket(-(-live // self.spec.page_size), self.spec.pages_per_slot)
        return None if gp >= self.spec.pages_per_slot else gp

    def _prefill_gather(self, Pb: int) -> Optional[int]:
        """Gather width for a scan prefill over a ``Pb``-bucket prompt:
        positions stay < Pb, and non-admitted rows' outputs are discarded,
        so the view only needs the prompt's own pages."""
        if self.gather_mode == "full" or not self._uses_gather():
            return None
        live = min(Pb, self.spec.seq_capacity)
        gp = _bucket(-(-live // self.spec.page_size), self.spec.pages_per_slot)
        return None if gp >= self.spec.pages_per_slot else gp

    def _prefill_fn(self, Pb: int):
        key = (Pb, self._prefill_gather(Pb))
        fn = self._prefill_cache.get(key)
        if fn is None:
            fn = jax.jit(spans.named(
                functools.partial(self._prefill_impl, gather_pages=key[1]),
                "serve_prefill"), donate_argnums=(1,))
            self._prefill_cache[key] = fn
        return fn

    def _gather_ladder(self) -> List[Optional[int]]:
        """Every gather width a run can request: the full-capacity arm
        plus (in bucket mode) each power-of-two rung below capacity."""
        ladder: List[Optional[int]] = [None]
        if self.gather_mode == "bucket" and self._uses_gather():
            gp = 1
            while gp < self.spec.pages_per_slot:
                ladder.append(gp)
                gp <<= 1
        return ladder

    def warmup(self, prompt_lens: Sequence[int] = ()) -> None:
        """Compile the decode-step gather ladder and the prefill buckets
        for the given prompt lengths without touching any state: an
        all-inactive call writes back exactly what it reads."""
        S = self.spec.num_slots
        table = jnp.asarray(self.alloc.tables)
        off = jnp.zeros((S,), jnp.int32)
        inact = jnp.zeros((S,), bool)
        for gp in self._gather_ladder():
            tok, self.pages = self._step_fn(gp)(
                self.params, self.pages, table,
                jnp.zeros((S, 1), jnp.int32), off, inact)
            jax.block_until_ready(tok)
        cap = self.spec.seq_capacity if self.window is None else None
        for p in sorted({_bucket(int(p), cap) for p in prompt_lens}):
            fn = self._prefill_fn(p)
            self.pages, toks = fn(self.params, self.pages, table,
                                  jnp.zeros((S, p), jnp.int32), off, inact)
            jax.block_until_ready(toks)

    def run(self, requests: Sequence[Request]) -> ServeReport:
        """Serve every request; returns per-request accounting. Each
        phase of the loop (admit, prefill, schedule, decode step, emit)
        runs in an ``obs.spans`` span, so a trace puts the loop's whole
        time down to one of them (docs/serving.md)."""
        cfg, spec, alloc = self.cfg, self.spec, self.alloc
        S = spec.num_slots
        cap = spec.seq_capacity
        reg = self.registry
        queue_wait = reg.series("serving.queue_wait_s")
        prefill_s = reg.series("serving.prefill_s")
        decode_s = reg.series("serving.decode_s")
        step_s = reg.series("serving.decode_step_s")
        latency_s = reg.series("serving.latency_s")
        occupancy = reg.series("serving.occupancy")
        prompt_ctr = reg.counter("serving.prefill_tokens")
        lane_ctr = reg.counter("serving.prefill_lane_tokens")
        done_ctr = reg.counter("serving.requests_completed")
        tok_ctr = reg.counter("serving.tokens_generated")
        if self._moe:
            # expert work per decode step and per prefill call (with each
            # admitted prompt's length under its call's index), so a
            # reader can take just the calls a profile holds
            hit_ser = reg.series("serving.moe_decode_experts_hit")
            dpair_ser = reg.series("serving.moe_decode_pairs")
            live_ser = reg.series("serving.decode_live_tokens")
            ppair_ser = reg.series("serving.moe_prefill_pairs")
            plen_ser = reg.series("serving.prefill_call_prompt_tokens")

        reqs = sorted(requests, key=lambda r: r.arrival)
        if self.window is None:
            for r in reqs:
                if len(r.prompt) + r.gen > cap:
                    raise ValueError(
                        f"request {r.rid}: prompt {len(r.prompt)} + gen "
                        f"{r.gen} exceeds cache capacity {cap}")

        slot_req: List[Optional[Request]] = [None] * S
        slot_pos = np.zeros(S, np.int32)       # next decode position
        slot_tok = np.zeros(S, np.int32)       # next input token
        slot_left = np.zeros(S, np.int64)      # decode steps remaining
        slot_pf_end = np.zeros(S, np.float64)  # prefill end (virtual clock)
        out_tokens: Dict[int, List[int]] = {}
        out_times: Dict[int, List[float]] = {}
        finished: Dict[int, dict] = {}

        t0 = monotonic()
        voff = 0.0
        now = lambda: monotonic() - t0 + voff
        qi = 0
        n_active = 0
        steps = 0
        occ_samples: List[int] = []

        def retire(s: int, tnow: float) -> None:
            nonlocal n_active
            r = slot_req[s]
            lat = tnow - r.arrival
            finished[r.rid] = {
                "arrival": r.arrival, "latency": lat,
                "queue_wait": finished[r.rid]["queue_wait"],
                "gen": len(out_tokens[r.rid])}
            latency_s.append(lat, step=r.rid)
            decode_s.append(tnow - slot_pf_end[s], step=r.rid)
            done_ctr.inc()
            alloc.release(s)
            slot_req[s] = None
            n_active -= 1

        while qi < len(reqs) or n_active:
            tnow = now()
            if (n_active == 0 and qi < len(reqs)
                    and reqs[qi].arrival > tnow):
                voff += reqs[qi].arrival - tnow    # idle: skip, don't sleep
                tnow = now()

            # -- admission: fill free slots from the arrived queue --------
            with spans.span("serve.admit"):
                admits: List[int] = []
                for s in range(S):
                    if qi >= len(reqs) or slot_req[s] is not None:
                        continue
                    r = reqs[qi]
                    need = min(len(r.prompt), cap)
                    if r.arrival > tnow or not alloc.can_fit(need):
                        if (n_active == 0 and not admits
                                and r.arrival <= tnow):
                            raise RuntimeError(
                                f"request {r.rid} cannot fit an empty pool")
                        break
                    alloc.ensure(s, need)
                    slot_req[s] = r
                    slot_pos[s] = 0
                    slot_left[s] = r.gen
                    out_tokens[r.rid] = []
                    out_times[r.rid] = []
                    finished[r.rid] = {"queue_wait": tnow - r.arrival}
                    queue_wait.append(tnow - r.arrival, step=r.rid)
                    admits.append(s)
                    qi += 1
                    n_active += 1
                if admits:
                    plens = np.array([len(slot_req[s].prompt) if slot_req[s]
                                      else 0 for s in range(S)], np.int32)
                    pmax = max(len(slot_req[s].prompt) for s in admits)
                    Pb = _bucket(pmax, cap if self.window is None else None)
                    prompts = np.zeros((S, Pb), np.int32)
                    admit = np.zeros(S, bool)
                    for s in admits:
                        r = slot_req[s]
                        prompts[s, :len(r.prompt)] = r.prompt[:Pb]
                        admit[s] = True
                    prompt_ctr.inc(int(plens[admit].sum()))
                    lane_ctr.inc(S * Pb)
                    admit_rids = [slot_req[s].rid for s in admits]

            # -- prefill the admitted slots (one bucketed jitted call) ----
            if admits:
                tpf = now()
                with spans.span("serve.prefill", lanes=len(admits),
                                bucket=Pb, rids=admit_rids):
                    fn = self._prefill_fn(Pb)
                    self.pages, toks = fn(
                        self.params, self.pages, jnp.asarray(alloc.tables),
                        jnp.asarray(prompts), jnp.asarray(plens),
                        jnp.asarray(admit))
                    if self._moe:
                        toks, pairs = jax.device_get(toks)   # one sync
                    else:
                        toks = np.asarray(toks)    # (Pb, S); sync
                tnow = now()
                with spans.span("serve.emit"):
                    if self._moe:
                        call = len(ppair_ser)
                        ppair_ser.append(int(pairs), step=call)
                        for s in admits:
                            plen_ser.append(len(slot_req[s].prompt), step=call)
                    for s in admits:
                        r = slot_req[s]
                        prefill_s.append(tnow - tpf, step=r.rid)
                        slot_pf_end[s] = tnow
                        first = int(toks[len(r.prompt) - 1, s])
                        out_tokens[r.rid].append(first)
                        out_times[r.rid].append(tnow)
                        tok_ctr.inc()
                        slot_tok[s] = first
                        slot_pos[s] = len(r.prompt)
                        slot_left[s] = r.gen - 1
                        if slot_left[s] == 0:
                            retire(s, tnow)

            if n_active == 0:
                continue

            # -- one continuous decode step over every live slot ----------
            with spans.span("serve.schedule"):
                active = np.array([r is not None for r in slot_req])
                for s in np.nonzero(active)[0]:
                    alloc.ensure(int(s), int(slot_pos[s]) + 1)
                occ_samples.append(int(active.sum()))
                occupancy.append(int(active.sum()), step=steps)
                gp = self._gather_bucket(slot_pos, active)
            tstep = now()
            with spans.span("serve.decode_step", occupancy=int(active.sum()),
                            gather=(gp if gp is not None
                                    else spec.pages_per_slot)):
                with spans.span("serve.decode.inputs"):
                    inputs = (jnp.asarray(alloc.tables),
                              jnp.asarray(slot_tok[:, None]),
                              jnp.asarray(slot_pos), jnp.asarray(active))
                with spans.span("serve.decode.launch"):
                    tok, self.pages = self._step_fn(gp)(
                        self.params, self.pages, *inputs)
                with spans.span("serve.decode.sync"):
                    if self._moe:
                        tok, counts = jax.device_get(tok)    # one sync
                    else:
                        tok = np.asarray(tok)
            tnow = now()
            with spans.span("serve.emit"):
                step_s.append(tnow - tstep, step=steps)
                if self._moe:
                    hit_ser.append(int(counts[0]), step=steps)
                    dpair_ser.append(int(counts[1]), step=steps)
                    # a slot at position p reads rows 0..p
                    live_ser.append(int(slot_pos[active].sum()
                                        + active.sum()), step=steps)
                steps += 1
                for s in np.nonzero(active)[0]:
                    r = slot_req[s]
                    out_tokens[r.rid].append(int(tok[s]))
                    out_times[r.rid].append(tnow)
                    tok_ctr.inc()
                    slot_tok[s] = int(tok[s])
                    slot_pos[s] += 1
                    slot_left[s] -= 1
                    if slot_left[s] == 0:
                        retire(int(s), tnow)

        rids = np.array(sorted(finished), np.int64)
        occ = np.array(occ_samples) if occ_samples else np.zeros(1)
        return ServeReport(
            mode="continuous",
            rids=rids,
            arrivals=np.array([finished[r]["arrival"] for r in rids]),
            queue_waits=np.array([finished[r]["queue_wait"] for r in rids]),
            latencies=np.array([finished[r]["latency"] for r in rids]),
            gen_counts=np.array([finished[r]["gen"] for r in rids]),
            tokens={r: np.array(out_tokens[r], np.int32) for r in rids},
            token_times={r: np.array(out_times[r], np.float64)
                         for r in rids},
            makespan=now(),
            occupancy_mean=float(occ.mean()))


# ---------------------------------------------------------------------------
# Static-batch baseline on the same trace
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _static_fns(cfg: ArchConfig, window):
    """Jitted prefill/decode shared across calls (ArchConfig is a frozen
    dataclass, hence hashable) so back-to-back trace runs — warmup then
    measured — reuse compiled code like the continuous server does."""
    pf = jax.jit(lambda p, c, t: T.prefill(p, c, t, cfg, window))
    dec = jax.jit(lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg,
                                                     window))
    return pf, dec


def static_serve_trace(cfg: ArchConfig, requests: Sequence[Request], *,
                       batch: int = 8, params=None, seed: int = 0,
                       window: Optional[int] = "config",
                       registry: Optional[MetricRegistry] = None
                       ) -> ServeReport:
    """The pre-continuous ``serve()`` flow run against a trace: requests
    are chunked into arrival-order batches; each batch waits for its last
    member, prefills padded prompts in one call, then decodes to the
    *longest* generation in the batch — no slot recycles early, every
    member's latency is the batch's end. The honest baseline the
    continuous server's goodput gate compares against."""
    if window == "config":
        window = cfg.sliding_window
    if params is None:
        params = T.init_params(jax.random.PRNGKey(seed), cfg)
    reg = registry if registry is not None else MetricRegistry()
    prefill_s = reg.series("serving.prefill_s")
    step_s = reg.series("serving.decode_step_s")
    latency_s = reg.series("serving.latency_s")

    pf, dec = _static_fns(cfg, window)
    reqs = sorted(requests, key=lambda r: r.arrival)
    groups = [reqs[i:i + batch] for i in range(0, len(reqs), batch)]

    finished: Dict[int, dict] = {}
    tokens: Dict[int, np.ndarray] = {}
    token_times: Dict[int, np.ndarray] = {}
    t0 = monotonic()
    voff = 0.0
    now = lambda: monotonic() - t0 + voff
    occ_num = 0.0
    occ_time = 0.0

    for grp in groups:
        last_arrival = max(r.arrival for r in grp)
        tnow = now()
        if last_arrival > tnow:                    # wait to fill the batch
            voff += last_arrival - tnow
            tnow = now()
        start = tnow
        pmax = _bucket(max(len(r.prompt) for r in grp))
        gmax = max(r.gen for r in grp)
        prompts = np.zeros((batch, pmax), np.int32)
        for i in range(batch):
            r = grp[min(i, len(grp) - 1)]          # pad lanes: repeat last
            prompts[i, :len(r.prompt)] = r.prompt
        total_cap = pmax + _bucket(gmax)     # bucket: bounded retraces
        cache = T.init_cache(cfg, batch, total_cap, window)
        tpf = now()
        logits, cache = jax.block_until_ready(
            pf(params, cache, jnp.asarray(prompts)))
        times = [now()]
        prefill_s.append(times[0] - tpf)
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        outs = [np.asarray(tok)[:, 0]]
        for t in range(pmax, pmax + gmax - 1):
            ts = now()
            logits, cache = dec(params, cache, tok, jnp.int32(t))
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
            tok = tok.astype(jnp.int32)
            outs.append(np.asarray(tok)[:, 0])     # sync
            times.append(now())
            step_s.append(times[-1] - ts)
        end = now()
        occ_num += len(grp) * (end - start)
        occ_time += end - start
        allt = np.stack(outs, axis=1)              # (batch, gmax)
        for i, r in enumerate(grp):
            finished[r.rid] = {"arrival": r.arrival,
                               "queue_wait": start - r.arrival,
                               "latency": end - r.arrival,
                               "gen": r.gen}
            latency_s.append(end - r.arrival, step=r.rid)
            tokens[r.rid] = allt[i, :r.gen].astype(np.int32)
            token_times[r.rid] = np.array(times[:r.gen], np.float64)

    rids = np.array(sorted(finished), np.int64)
    makespan = now()
    return ServeReport(
        mode="static",
        rids=rids,
        arrivals=np.array([finished[r]["arrival"] for r in rids]),
        queue_waits=np.array([finished[r]["queue_wait"] for r in rids]),
        latencies=np.array([finished[r]["latency"] for r in rids]),
        gen_counts=np.array([finished[r]["gen"] for r in rids]),
        tokens=tokens,
        token_times=token_times,
        makespan=makespan,
        occupancy_mean=occ_num / occ_time / batch if occ_time else 0.0)
