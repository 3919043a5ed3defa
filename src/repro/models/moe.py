"""Mixture-of-Experts layer: top-k router with capacity-based dispatch
(Switch/GShard style), optional shared experts (Qwen-MoE), and router
load-balance auxiliary loss.

TPU-shaped implementation choices:
  - dispatch is **batch-local and sequence-chunked** (lax.scan over chunks of
    ``MOE_CHUNK`` tokens): the position-in-expert cumsum never crosses a
    shard boundary, so under batch sharding the whole dispatch lowers
    without cross-chip scans, and the (tokens, E, capacity) one-hots stay
    VMEM-scale. Capacity is capped at ``MAX_CAPACITY`` (token dropping,
    standard for capacity-factor MoE).
  - expert FFN hidden dim is the tensor-sharded axis (always divisible by
    the model axis, unlike expert count: 60 experts vs 16-wide axis).
Expert-parallel all-to-all is a recorded beyond-paper optimization candidate
(EXPERIMENTS.md §Perf).

That capacity dispatch is the training layer (``moe_forward``, run by
``lm_loss``). Serving runs ``moe_dropless``: it routes over the whole
layer's experts at full router width, keeps the (token, expert) pairs of
the experts held here (the first ``num_experts`` of the layer's, the
chip's share under expert parallelism), sorts them by expert and computes them with
one grouped matmul (``jax.lax.ragged_dot``), dropping no token. What
the experts held elsewhere would add is left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

CAPACITY_FACTOR = 1.25
MOE_CHUNK = 4096
MAX_CAPACITY = 1024


def init_moe(key, cfg: ArchConfig):
    assert cfg.moe is not None
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    dt = cfg.dtype("param")
    ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(ks[0], (d, m.router_width))
                   * d ** -0.5).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * d ** -0.5).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * d ** -0.5).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * f ** -0.5).astype(dt),
    }
    if m.num_shared_experts > 0:
        fs = m.num_shared_experts * f
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": (jax.random.normal(ks2[0], (d, fs)) * d ** -0.5).astype(dt),
            "w_up": (jax.random.normal(ks2[1], (d, fs)) * d ** -0.5).astype(dt),
            "w_down": (jax.random.normal(ks2[2], (fs, d)) * fs ** -0.5).astype(dt),
        }
    return p


def capacity(tokens_per_row: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(m.top_k * tokens_per_row * CAPACITY_FACTOR / m.num_experts)
    return max(1, min(c, MAX_CAPACITY))


def _chunk_moe(p, xk, cfg: ArchConfig):
    """One chunk. xk: (B, L, D) -> (y, aux_stats)."""
    m = cfg.moe
    if m.router_width != m.num_experts or not m.norm_topk_prob:
        raise ValueError("the capacity dispatch holds every expert and "
                         "renormalises its gates; serve this config "
                         "through moe_dropless")
    cd = cfg.dtype("compute")
    b, L, d = xk.shape
    e, k = m.num_experts, m.top_k
    cap = capacity(L, cfg)

    logits = (xk.astype(jnp.float32) @ p["router"])          # (B,L,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # (B,L,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)

    from repro.sharding.rules import constrain_batch
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)    # (B,L,k,E)
    flat = constrain_batch(onehot.reshape(b, L * k, e))
    # position within each expert's buffer (cumsum stays inside the row ->
    # batch-local under sharding)
    pos = jnp.cumsum(flat, axis=1) * flat - 1                # (B,Lk,E)
    keep = (pos < cap) & (flat > 0)
    pos_oh = jax.nn.one_hot(pos, cap, dtype=cd) * keep[..., None]  # (B,Lk,E,C)
    pos_oh = constrain_batch(pos_oh)
    gates_flat = jnp.repeat(gate_vals.reshape(b, L, k), 1, axis=-1) \
                    .reshape(b, L * k).astype(cd)
    x_rep = constrain_batch(jnp.repeat(xk, k, axis=1))       # (B,Lk,D)

    xin = constrain_batch(
        jnp.einsum("btec,btd->becd", pos_oh, x_rep))         # (B,E,C,D)
    gate = jax.nn.silu(jnp.einsum("becd,edf->becf", xin, p["w_gate"].astype(cd)))
    up = jnp.einsum("becd,edf->becf", xin, p["w_up"].astype(cd))
    out = jnp.einsum("becf,efd->becd", gate * up, p["w_down"].astype(cd))
    # combine back: weight each (token, choice) by its gate
    y = jnp.einsum("btec,bt,becd->btd", pos_oh, gates_flat, out)
    y = y.reshape(b, L, k, d).sum(axis=2)

    # GShard load-balance stats (summed over chunks by the caller)
    frac_tokens = onehot.sum(axis=2).astype(jnp.float32).mean(axis=(0, 1))
    mean_prob = probs.mean(axis=(0, 1))
    return y, (frac_tokens, mean_prob)


def _swiglu(x, w_gate, w_up, w_down, cd):
    h = jax.nn.silu(x @ w_gate.astype(cd)) * (x @ w_up.astype(cd))
    return h @ w_down.astype(cd)


def moe_forward(p, x, cfg: ArchConfig, chunk: int = MOE_CHUNK):
    """x: (B, S, D). Returns (y, aux_loss)."""
    m = cfg.moe
    cd = cfg.dtype("compute")
    b, s, d = x.shape
    L = min(chunk, s)
    e = m.num_experts

    if s % L or s == L:
        y, (ft, mp) = _chunk_moe(p, x, cfg)
        aux = e * jnp.sum(ft * mp)
    else:
        nc = s // L
        xc = x.reshape(b, nc, L, d).transpose(1, 0, 2, 3)

        def body(carry, xk):
            y, (ft, mp) = _chunk_moe(p, xk, cfg)
            return (carry[0] + ft, carry[1] + mp), y
        (ft, mp), yc = jax.lax.scan(
            body, (jnp.zeros((e,)), jnp.zeros((e,))), xc)
        y = yc.transpose(1, 0, 2, 3).reshape(b, s, d)
        aux = e * jnp.sum((ft / nc) * (mp / nc))

    if "shared" in p:
        sp = p["shared"]
        y = y + _swiglu(x.reshape(b * s, d), sp["w_gate"], sp["w_up"],
                        sp["w_down"], cd).reshape(b, s, d)

    return y, aux * m.router_aux_weight


def moe_dropless(p, x, cfg: ArchConfig, mask=None, layer=None):
    """The expert layer serving runs. x: (..., D); ``mask`` (x's leading
    shape, bool): the tokens whose routed pairs are computed (padding
    and idle slots are left out; their routed part reads 0). With
    ``layer`` (an int32 scalar), p's expert weights are every layer's,
    stacked ``(L, E, ...)``, and this layer's are read in place: the
    grouped matmul takes all ``L * E`` experts as its groups, this
    layer's the only ones given rows, so it fetches just the experts
    hit. (A layer scan's slice of the stack would be copied whole, all
    E experts, before the kernel could read it.)

    Router logits in float32 over all ``router_width`` experts, softmax,
    greedy top-k, gates renormalised only if ``norm_topk_prob``. The
    pairs whose expert is held here run through the grouped matmul in
    expert order; every other pair adds nothing. The shared experts then
    add their SwiGLU for every token. Returns (y, (experts_hit, pairs)):
    held experts given at least one pair, and the pairs computed."""
    m = cfg.moe
    cd = cfg.dtype("compute")
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d).astype(cd)
    n, k, e = xt.shape[0], m.top_k, m.num_experts
    with jax.named_scope("moe.route"):
        logits = jnp.dot(xt.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)                 # (N, k)
        if m.norm_topk_prob:
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-20)
        held = idx < e
        if mask is not None:
            held &= mask.reshape(n, 1)
        group = jnp.where(held, idx, e).reshape(-1)          # e: not here
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=e + 1)[:e].astype(jnp.int32)
        pairs = sizes.sum()
    w_gate, w_up, w_down = (p[n_].astype(cd)
                            for n_ in ("w_gate", "w_up", "w_down"))
    groups = sizes
    if layer is not None:
        stack = w_gate.shape[0]
        w_gate, w_up, w_down = (w.reshape((stack * e,) + w.shape[2:])
                                for w in (w_gate, w_up, w_down))
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros(stack * e, jnp.int32), sizes, (layer * e,))
    with jax.named_scope("moe.experts"):
        xs = xt[order // k]                                  # (N*k, D)
        h = (jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, groups))
             * jax.lax.ragged_dot(xs, w_up, groups))
        out = jax.lax.ragged_dot(h, w_down, groups)
        # rows past the held pairs belong to no group
        out = jnp.where((jnp.arange(n * k) < pairs)[:, None], out, 0)
        out = out[jnp.argsort(order)].reshape(n, k, d)       # token order
        w = jnp.where(held, gates, 0.0)
        y = jnp.einsum("nk,nkd->nd", w, out.astype(jnp.float32))
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            sp = p["shared"]
            y = y + _swiglu(xt, sp["w_gate"], sp["w_up"], sp["w_down"],
                            cd).astype(jnp.float32)
    hit = (sizes > 0).sum()
    return y.astype(cd).reshape(*lead, d), (hit, pairs)
