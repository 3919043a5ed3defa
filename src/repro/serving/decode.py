"""Paged decode: ``models.layers.attention_decode`` generalized to a
per-request position vector over a page-table-indirected cache.

Bitwise contract (pinned in ``tests/test_serving.py``): gathering a
slot's pages yields exactly the dense ``(B, W, K, hd)`` ring buffer, the
validity mask is the reference mask evaluated per batch row, and every
einsum/softmax runs the same shapes in the same order — so logits from
``paged_decode_step`` bit-match ``models.transformer.decode_step`` on the
dense cache whenever the per-row positions agree. Masked (out-of-range /
never-written / scratch-backed) cache entries cannot leak: their scores
sit at ``-1e30`` so ``exp`` underflows to exactly ``0.0`` in fp32 before
the value gather.

Writes are recycle-safe by construction: gather the old page entry,
``where(active, new, old)``, scatter back. Inactive slots' tables point
at the reserved scratch page 0, so colliding scatter indices always carry
identical payloads and the step stays deterministic as requests join and
leave the batch — one compiled step, any population.

The whole ``(L, P, page, K, hd)`` pools ride the layer scan's carry, and
each layer scatters its B new rows into them in place at ``[l, pid,
in_page]``. Passed as the scan's xs and returned as its ys instead, every
step would slice each layer's pool out of the input, write it whole into
a fresh stacked ys buffer and copy that into the (donated) output: three
passes over the entire pool to store one token per slot per layer.

Attention implementations (``attn_impl``):

- ``"pallas"`` — the in-kernel paged flash-decode
  (``repro.kernels.paged_attention``): the K/V BlockSpec index maps walk
  the page table inside the kernel, pages are consumed in place with no
  dense copy, per-row ``pos`` bounds the live page walk, and a
  ring-aware mask covers sliding windows — no fallback.
- ``"xla"`` — the masked dense-gather reference. ``gather_pages``
  (static) narrows the gather to the batch's live high-water page count:
  the view becomes the FIRST ``gather_pages`` ring slots and the mask its
  matching columns, so bandwidth follows live context even without
  Pallas. ``gather_pages=None`` (or ``= max_pages``) is the full-width
  bitwise baseline arm; narrowed widths re-tile XLA's reductions, so
  cross-width equality is token-level, like any batch-width change.
- ``"pallas_gather"`` — the legacy hot path kept as a bench arm: the
  ``flash_attention`` kernel over the full gathered copy
  (``q_offsets=pos``). Flash-on-a-copy requires a full (non-ring) cache:
  under a sliding window the ring wraps and slot order no longer equals
  position order, so this arm falls back to the XLA masked path — the
  server surfaces that fallback (warning + obs note) instead of hiding
  it.

Latent attention (``ArchConfig.mla``) decodes in the absorbed form
(``models.mla.mla_decode``) from one ``(L, P, page, latent_dim)`` pool
of ``[c | k_pe]`` rows on the same tables, carried through the layer
scans (the leading dense layers', then the routed layers') the same way;
it reads only those rows, through the ``"xla"`` gather, and each step
writes one row a slot and layer. Every routed layer runs the dropless
expert layer (``models.moe.moe_dropless``) over the active slots.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.paged_attention.ref import valid_mask as _valid_mask
from repro.models import layers as L
from repro.models import mla as A
from repro.models import moe as M

ATTN_IMPLS = ("xla", "pallas", "pallas_gather")


def paged_attention_decode(p, x, k_pool, v_pool, layer, table, pos, active,
                           cfg: ArchConfig, *, window: Optional[int] = None,
                           attn_impl: str = "xla",
                           gather_pages: Optional[int] = None):
    """One layer's decode over the paged pool.

    x: (B,1,D) hidden; k_pool/v_pool: (L, P, page, K, hd) every layer's
    pool; layer: int32 scalar, this layer's index into them; table: (B,
    max_pages) int32 page ids (0 = scratch); pos: (B,) int32 absolute
    position per slot; active: (B,) bool live-request mask.
    ``gather_pages`` (static, XLA path only): gather just the first
    ``gather_pages`` table columns — must cover every live row's pages
    (the server's bucket ladder guarantees it).
    Returns (out (B,1,D), (k_pool, v_pool)) with this layer's rows written.
    """
    cd = cfg.dtype("compute")
    B = x.shape[0]
    _, _, page, K, hd = k_pool.shape
    max_pages = table.shape[1]
    W = max_pages * page

    q, k, v = L._project_qkv(p, x, None, cfg)
    posb = pos[:, None].astype(jnp.int32)            # (B, 1)
    q = L.rope(q, posb, cfg.rope_theta)
    k = L.rope(k, posb, cfg.rope_theta)

    slot = pos % W if window is not None else pos
    page_idx = slot // page
    in_page = slot % page
    pid = jnp.take_along_axis(table, page_idx[:, None], axis=1)[:, 0]  # (B,)

    kn = k[:, 0].astype(k_pool.dtype)                # (B, K, hd)
    vn = v[:, 0].astype(v_pool.dtype)
    act = active[:, None, None]
    oldk = k_pool[layer, pid, in_page]
    oldv = v_pool[layer, pid, in_page]
    k_pool = k_pool.at[layer, pid, in_page].set(jnp.where(act, kn, oldk))
    v_pool = v_pool.at[layer, pid, in_page].set(jnp.where(act, vn, oldv))

    if attn_impl == "pallas":
        from repro.kernels.paged_attention import ops as pa_ops
        out = pa_ops.paged_attention(
            q, jax.lax.dynamic_index_in_dim(k_pool, layer, keepdims=False),
            jax.lax.dynamic_index_in_dim(v_pool, layer, keepdims=False),
            table, pos, window=window)
    else:
        gp = max_pages if gather_pages is None else min(gather_pages,
                                                        max_pages)
        tb = table if gp == max_pages else table[:, :gp]
        Wb = gp * page
        ck = k_pool[layer, tb].reshape(B, Wb, K, hd)  # the dense ring view
        cv = v_pool[layer, tb].reshape(B, Wb, K, hd)
        if attn_impl == "pallas_gather" and window is None:
            from repro.kernels.flash_attention import ops as fa_ops
            out = fa_ops.flash_attention(q, ck.astype(cd), cv.astype(cd),
                                         causal=True, q_offsets=pos)
        else:
            # the mask is the full-ring reference evaluated per row, cut
            # to the gathered columns (the first Wb ring slots)
            valid = _valid_mask(pos, W, window)[:, :Wb]
            scores = L._grouped_scores(q, ck.astype(cd)).astype(jnp.float32)
            scores = scores + jnp.where(valid, 0.0,
                                        -1e30)[:, None, None, None, :]
            w = jax.nn.softmax(scores, axis=-1).astype(cd)
            out = L._apply_scores(w, cv.astype(cd))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd))
    return y, (k_pool, v_pool)


def paged_mla_decode(p, x, pool, layer, table, pos, active, cfg: ArchConfig,
                     *, gather_pages: Optional[int] = None):
    """One latent-attention layer's decode over the latent pool.

    pool: (L, P, page, latent_dim) every layer's rows; the rest as
    ``paged_attention_decode``. Writes each active slot's new row, then
    attends over the first ``gather_pages`` pages of its table.
    Returns (out (B,1,D), pool)."""
    B = x.shape[0]
    page, C = pool.shape[2], pool.shape[3]
    max_pages = table.shape[1]
    row = A.latent_rows(p, x, pos[:, None], cfg)[:, 0].astype(pool.dtype)
    pid = jnp.take_along_axis(table, (pos // page)[:, None], axis=1)[:, 0]
    in_page = pos % page
    old = pool[layer, pid, in_page]
    pool = pool.at[layer, pid, in_page].set(
        jnp.where(active[:, None], row, old))
    gp = max_pages if gather_pages is None else min(gather_pages, max_pages)
    rows = pool[layer, table[:, :gp]].reshape(B, gp * page, C)
    valid = _valid_mask(pos, max_pages * page, None)[:, :gp * page]
    return A.mla_decode(p, x, rows, valid, pos, cfg), pool


def _held_experts(blocks):
    """Routed layers' blocks without their expert weights, and those
    weights as every layer's stack: the layer scan slices the first, and
    ``moe_dropless(..., layer=)`` reads the second in place."""
    names = ("w_gate", "w_up", "w_down")
    moe = blocks["moe"]
    return (dict(blocks, moe={n: v for n, v in moe.items() if n not in names}),
            {n: moe[n] for n in names})


def paged_decode_step(params, pages, table, tokens, pos, active,
                      cfg: ArchConfig, *, window: Optional[int] = None,
                      attn_impl: str = "xla",
                      gather_pages: Optional[int] = None,
                      stats: Optional[dict] = None):
    """One continuous-batching decode step for dense/moe stacks.

    pages: {"k","v"}: (L, P, page, K, hd), or {"latent"} under MLA;
    table: (B, max_pages) shared by all layers; tokens: (B,1) int32; pos:
    (B,) int32; active: (B,) bool. Returns (logits (B,1,V) fp32, new
    pages). Mirrors ``transformer.decode_step``'s layer scan so the math
    bit-matches; the pools are carried through it and written in place
    (module docstring). A routed stack puts its step's counts into
    ``stats`` when given: ``experts_hit`` (held experts given a pair,
    summed over layers) and ``pairs`` ((token, held expert) pairs), values
    of the same traced computation.
    """
    if window is None:
        window = cfg.sliding_window
    if attn_impl not in ATTN_IMPLS:
        raise ValueError(f"attn_impl must be one of {ATTN_IMPLS}, "
                         f"not {attn_impl!r}")
    t = cfg.arch_type
    if t not in ("dense", "moe"):
        raise ValueError(f"paged decode supports dense/moe, not {t!r}")
    if cfg.mla and (attn_impl != "xla" or window is not None):
        raise ValueError("latent attention decodes through the xla "
                         "gather over a full (non-ring) cache")
    nd = cfg.first_k_dense
    blocks, experts = ((params["blocks"], None) if t == "dense"
                       else _held_experts(params["blocks"]))

    def attend(h, pages, bp, layer):
        hn = L.rms_norm(h, bp["ln1"], cfg.norm_eps)
        if cfg.mla:
            a, pool = paged_mla_decode(bp["attn"], hn, pages["latent"], layer,
                                       table, pos, active, cfg,
                                       gather_pages=gather_pages)
            return a, {"latent": pool}
        a, (kp, vp) = paged_attention_decode(
            bp["attn"], hn, pages["k"], pages["v"], layer, table, pos, active,
            cfg, window=window, attn_impl=attn_impl, gather_pages=gather_pages)
        return a, {"k": kp, "v": vp}

    def body(carry, xs):                  # a leading dense or a stacked layer
        h, pages = carry
        bp, layer = xs
        a, pages = attend(h, pages, bp, layer)
        h = h + a
        h2 = L.rms_norm(h, bp["ln2"], cfg.norm_eps)
        if "mlp" in bp:
            return (h + L.mlp_forward(bp["mlp"], h2, cfg), pages), None
        y, counts = M.moe_dropless(dict(bp["moe"], **experts), h2, cfg,
                                   active[:, None], layer=layer - nd)
        return (h + y, pages), counts

    x = L.embed(params["embed"], tokens, cfg)
    n_layers = next(iter(pages.values())).shape[0]
    if nd:
        (x, pages), _ = jax.lax.scan(
            body, (x, pages),
            (params["dense_blocks"], jnp.arange(nd, dtype=jnp.int32)))
    (x, pages), counts = jax.lax.scan(
        body, (x, pages), (blocks, jnp.arange(nd, n_layers, dtype=jnp.int32)))
    if stats is not None and counts is not None:
        stats.update(experts_hit=counts[0].sum(), pairs=counts[1].sum())
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return L.unembed(params["embed"], x, cfg), pages
