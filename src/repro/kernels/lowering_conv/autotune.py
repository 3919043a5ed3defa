"""Per-layer tile autotuning for the lowering conv (paper Fig. 4: the b_p
sweep, automated).

``autotune_tiles`` probes the ``choose_tiles``-resolved (b_p, r_b)
candidates that fit under the ``vmem_bytes`` budget by timing the actual
op (forward + backward through the custom VJP) with ``engine.timing``,
and caches the winner per (input shape, kernel shape, stride).
Model code (``models.cnn._conv``) looks the cached choice up at trace
time via ``cached_tiles`` and falls back to the defaults when the layer
was never probed.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.engine import timing
from repro.kernels.lowering_conv.lowering_conv import choose_tiles, vmem_bytes

DEFAULT_TILES = (8, 8)
# generous CPU-probe default; on real TPU pass the core's VMEM (~16 MB)
# minus headroom for double buffering
DEFAULT_BUDGET_BYTES = 4 << 20

# geometry key -> ((b_p, r_b), budget_bytes the probe ran under)
_TILE_CACHE: Dict[tuple, Tuple[Tuple[int, int], int]] = {}


def _cache_key(x_shape, w_shape, stride: int) -> tuple:
    """Keyed on the layer geometry WITHOUT the batch dimension: the engine
    traces the same conv at batch/g (group vmap) or batch/(g*k) (per-device
    shard), and a (b_p, r_b) probed at the global batch stays valid at any
    of them — ``choose_tiles`` re-clamps b_p to a divisor of whatever batch
    the kernel actually sees."""
    return (tuple(x_shape)[1:], tuple(w_shape), int(stride))


def clear_tile_cache() -> None:
    _TILE_CACHE.clear()


def cached_tiles(x_shape, w_shape, stride: int) -> Tuple[int, int]:
    """The autotuned (b_p, r_b) for this layer geometry (batch-agnostic —
    see ``_cache_key``), or the defaults if it was never probed."""
    hit = _TILE_CACHE.get(_cache_key(x_shape, w_shape, stride))
    return hit[0] if hit is not None else DEFAULT_TILES


def _max_vmem(bp: int, rb: int, x_shape, w_shape, stride: int,
              itemsize: int = 4) -> int:
    """Worst-case working set of (b_p, r_b) across fwd/wgrad/dgrad."""
    _, h, w, cin = x_shape
    kh, kw, _, cout = w_shape
    geom = dict(h=h, w=w, cin=cin, kh=kh, kw=kw, cout=cout, stride=stride,
                itemsize=itemsize)
    return max(vmem_bytes(bp=bp, rb=rb, pass_=p, **geom)
               for p in ("fwd", "wgrad", "dgrad"))


def tile_candidates(x_shape, w_shape, stride: int, *,
                    budget_bytes: int = DEFAULT_BUDGET_BYTES,
                    itemsize: int = 4) -> List[Tuple[int, int]]:
    """Distinct (b_p, r_b) divisor pairs whose forward AND backward VMEM
    working sets (``vmem_bytes`` pass_ = fwd / wgrad / dgrad) fit the
    budget. Always contains at least (1, 1)."""
    b, h, w, cin = x_shape
    kh, kw, _, cout = w_shape
    ho = (h - kh) // stride + 1
    geom = dict(h=h, w=w, cin=cin, kh=kh, kw=kw, cout=cout, stride=stride,
                itemsize=itemsize)
    seen, out = set(), []
    for bp_req in sorted({1, 2, 4, 8, 16, 32, b}):
        for rb_req in sorted({1, 2, 4, 8, 16, ho}):
            bp, rb = choose_tiles(b, ho, bp_req, rb_req)
            if (bp, rb) in seen:
                continue
            seen.add((bp, rb))
            need = max(vmem_bytes(bp=bp, rb=rb, pass_=p, **geom)
                       for p in ("fwd", "wgrad", "dgrad"))
            if need <= budget_bytes:
                out.append((bp, rb))
    if not out:
        out = [(1, 1)]
    return sorted(out)


def autotune_tiles(x_shape, w_shape, stride: int = 1, *,
                   budget_bytes: int = DEFAULT_BUDGET_BYTES,
                   warmup: int = 1, iters: int = 3,
                   key: Optional[jax.Array] = None) -> Tuple[int, int]:
    """Probe every in-budget tile candidate on the real op (forward +
    backward, jit-compiled) and cache the fastest. Idempotent per layer:
    a cache hit returns immediately without re-probing — unless the
    cached choice no longer fits a (smaller) ``budget_bytes``, which
    forces a re-probe under the new budget. (A larger budget keeps the
    cached choice: still valid, possibly conservative.)"""
    ck = _cache_key(x_shape, w_shape, stride)
    hit = _TILE_CACHE.get(ck)
    if hit is not None:
        tiles, probed_budget = hit
        if budget_bytes >= probed_budget or \
                _max_vmem(*tiles, x_shape, w_shape, stride) <= budget_bytes:
            return tiles
    from repro.kernels.lowering_conv import ops   # circular-at-import guard

    if key is None:
        key = jax.random.PRNGKey(0)
    kx, kw_ = jax.random.split(key)
    x = jax.random.normal(kx, x_shape, jnp.float32)
    w = jax.random.normal(kw_, w_shape, jnp.float32) * 0.1

    def step_for(bp, rb):
        def fwd_bwd(x, w):
            y, vjp = jax.vjp(
                lambda x, w: ops.lowering_conv(x, w, stride=stride, bp=bp,
                                               rb=rb),
                x, w)
            return jax.tree.map(jnp.sum, vjp(jnp.ones_like(y)))
        return jax.jit(fwd_bwd)

    from repro.obs import spans
    cands = tile_candidates(x_shape, w_shape, stride,
                            budget_bytes=budget_bytes)
    with spans.span("autotune.conv_tiles", candidates=len(cands),
                    x_shape=tuple(x_shape), w_shape=tuple(w_shape),
                    stride=stride) as outer:
        best, best_t = DEFAULT_TILES, float("inf")
        for bp, rb in cands:
            step = step_for(bp, rb)
            with spans.span("autotune.candidate", bp=bp, rb=rb) as sp:
                stats = timing.probe(lambda: step(x, w), warmup=warmup,
                                     iters=iters)
                sp.set(min_us=stats.min_s * 1e6)
            if stats.min_s < best_t:
                best, best_t = (bp, rb), stats.min_s
        outer.set(best_bp=best[0], best_rb=best[1],
                  best_min_us=best_t * 1e6)
    _TILE_CACHE[ck] = (best, budget_bytes)
    return best
