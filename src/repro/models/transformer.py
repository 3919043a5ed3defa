"""Unified language-model assembly for all assigned architecture families.

arch_type:
  dense  — (norm, GQA attn, norm, MLP) x L
  moe    — (norm, GQA attn, norm, MoE) x L; with ``first_k_dense`` the
           first layers hold a dense MLP instead ("dense_blocks"), and
           with ``mla`` every layer's attention is latent (``models.mla``)
  ssm    — (norm, SSD) x L                              (attention-free)
  hybrid — Griffin super-blocks (rec, rec, local-attn) cyclic
  vlm    — decoder with a cross-attn layer every `cross_attn_every` layers
  encdec — Whisper: encoder (non-causal) + decoder (causal + cross)

All homogeneous stacks run under ``lax.scan`` over stacked layer params so
compile time is depth-independent; blocks are wrapped in ``jax.checkpoint``
when cfg.remat. Params are nested dicts; caches mirror the layer structure.

API:
  init_params(key, cfg)                         -> params
  forward(params, batch, cfg, return_cache=...) -> (logits, aux, cache|None)
  init_cache(cfg, batch, seq_len)               -> cache pytree (decode)
  decode_step(params, cache, tokens, pos, cfg)  -> (logits, new_cache)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import mla as A
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.sharding.rules import constrain_batch


# ---------------------------------------------------------------------------
# Per-block init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ArchConfig):
    return A.init_mla(key, cfg) if cfg.mla else L.init_attention(key, cfg)


def _init_dense_block(key, cfg: ArchConfig):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "attn": _init_attn(k1, cfg),
            "ln2": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "mlp": L.init_mlp(k2, cfg)}


def _init_moe_block(key, cfg: ArchConfig):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "attn": _init_attn(k1, cfg),
            "ln2": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "moe": M.init_moe(k2, cfg)}


def _init_ssm_block(key, cfg: ArchConfig):
    return {"ln": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "ssm": S.init_ssm(key, cfg)}


def _init_rec_block(key, cfg: ArchConfig):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "rec": R.init_rglru_block(k1, cfg),
            "ln2": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "mlp": L.init_mlp(k2, cfg)}


def _init_cross_block(key, cfg: ArchConfig):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "cross": L.init_attention(k1, cfg, cross=True),
            "ln2": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "mlp": L.init_mlp(k2, cfg)}


def _init_encdec_dec_block(key, cfg: ArchConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "attn": L.init_attention(k1, cfg),
            "ln2": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "cross": L.init_attention(k2, cfg, cross=True),
            "ln3": L.init_rms_norm(cfg.d_model, cfg.dtype("param")),
            "mlp": L.init_mlp(k3, cfg)}


def _stack(init_fn, key, n, cfg):
    return jax.vmap(lambda k: init_fn(k, cfg))(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# init_params
# ---------------------------------------------------------------------------

def _hybrid_counts(cfg: ArchConfig):
    plen = len(cfg.hybrid.pattern)
    n_super = cfg.num_layers // plen
    n_rem = cfg.num_layers - n_super * plen
    return plen, n_super, n_rem


def _vlm_counts(cfg: ArchConfig):
    per = cfg.cross_attn_every
    n_super = cfg.num_layers // per
    n_rem = cfg.num_layers - n_super * per
    return per, n_super, n_rem


def init_params(key, cfg: ArchConfig):
    kemb, kblocks, kextra, kfin = jax.random.split(key, 4)
    params = {"embed": L.init_embed(kemb, cfg),
              "ln_f": L.init_rms_norm(cfg.d_model, cfg.dtype("param"))}
    t = cfg.arch_type
    if t in ("dense",):
        params["blocks"] = _stack(_init_dense_block, kblocks, cfg.num_layers, cfg)
    elif t == "moe":
        nd = cfg.first_k_dense
        params["blocks"] = _stack(_init_moe_block, kblocks,
                                  cfg.num_layers - nd, cfg)
        if nd:
            params["dense_blocks"] = _stack(_init_dense_block, kextra, nd, cfg)
    elif t == "ssm":
        params["blocks"] = _stack(_init_ssm_block, kblocks, cfg.num_layers, cfg)
    elif t == "hybrid":
        plen, n_super, n_rem = _hybrid_counts(cfg)
        n_rec = sum(1 for x in cfg.hybrid.pattern if x == "rec")
        params["super"] = {
            "rec": _stack(lambda k, c: _stack(_init_rec_block, k, n_rec, c),
                          kblocks, n_super, cfg),
            "attn": _stack(_init_dense_block, kextra, n_super, cfg),
        }
        if n_rem:
            params["rem"] = _stack(_init_rec_block, kfin, n_rem, cfg)
    elif t == "vlm":
        per, n_super, n_rem = _vlm_counts(cfg)
        params["super"] = {
            "self": _stack(lambda k, c: _stack(_init_dense_block, k, per - 1, c),
                           kblocks, n_super, cfg),
            "cross": _stack(_init_cross_block, kextra, n_super, cfg),
        }
        if n_rem:
            params["rem"] = _stack(_init_dense_block, kfin, n_rem, cfg)
    elif t == "encdec":
        params["enc"] = _stack(_init_dense_block, kblocks,
                               cfg.encoder_layers, cfg)
        params["enc_ln"] = L.init_rms_norm(cfg.d_model, cfg.dtype("param"))
        params["blocks"] = _stack(_init_encdec_dec_block, kextra,
                                  cfg.num_layers, cfg)
    else:
        raise ValueError(t)
    return params


# ---------------------------------------------------------------------------
# Block applications (x -> x), written to be scanned
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ArchConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _attention(p, x, cfg, *, window, attn_impl):
    """Self-attention over the sequence -> (out, cache rows): (k, v), or
    under MLA the latent rows."""
    if cfg.mla:
        return A.mla_forward(p, x, cfg)
    return L.attention_forward(p, x, cfg, window=window, attn_impl=attn_impl)


def _dense_block(bp, x, cfg, *, window=None, attn_impl="xla", collect=False):
    h, kv = _attention(bp["attn"], L.rms_norm(x, bp["ln1"], cfg.norm_eps),
                       cfg, window=window, attn_impl=attn_impl)
    x = x + h
    x = x + L.mlp_forward(bp["mlp"], L.rms_norm(x, bp["ln2"], cfg.norm_eps), cfg)
    return constrain_batch(x), (kv if collect else None)


def _moe_block(bp, x, cfg, *, window=None, attn_impl="xla", collect=False,
               mask=None):
    """A routed layer. Collecting a cache (serving's prefill) runs the
    dropless expert layer over ``mask``'s tokens and gives its pair count
    as the third output; training runs the capacity dispatch and gives
    its load-balance loss."""
    h, kv = _attention(bp["attn"], L.rms_norm(x, bp["ln1"], cfg.norm_eps),
                       cfg, window=window, attn_impl=attn_impl)
    x = x + h
    h2 = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
    if collect:
        y, (_, pairs) = M.moe_dropless(bp["moe"], h2, cfg, mask)
        return constrain_batch(x + y), pairs, kv
    y, aux = M.moe_forward(bp["moe"], h2, cfg)
    return constrain_batch(x + y), aux, None


def _ssm_block(bp, x, cfg, collect=False):
    y, hf = S.ssm_forward(bp["ssm"], L.rms_norm(x, bp["ln"], cfg.norm_eps), cfg)
    return constrain_batch(x + y), (hf if collect else None)


def _rec_block(bp, x, cfg, collect=False):
    y, hf = R.rglru_forward(bp["rec"], L.rms_norm(x, bp["ln1"], cfg.norm_eps), cfg)
    x = x + y
    x = x + L.mlp_forward(bp["mlp"], L.rms_norm(x, bp["ln2"], cfg.norm_eps), cfg)
    return constrain_batch(x), (hf if collect else None)


def _cross_block(bp, x, src, cfg, collect=False):
    h, kv = L.attention_forward(bp["cross"], L.rms_norm(x, bp["ln1"], cfg.norm_eps),
                                cfg, causal=False, kv_src=src)
    x = x + h
    x = x + L.mlp_forward(bp["mlp"], L.rms_norm(x, bp["ln2"], cfg.norm_eps), cfg)
    return constrain_batch(x), (kv if collect else None)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, batch, cfg: ArchConfig, *, return_cache: bool = False,
            attn_impl: str = "xla", window: Optional[int] = None):
    """batch: {"tokens": (B,S) int32} + "enc_emb" (encdec) / "img_emb" (vlm);
    for moe, optionally "mask" (B,S) bool: the tokens whose routed experts
    a prefill computes. Returns (logits fp32 (B,S,V), aux_loss scalar,
    cache-or-None). The cache is {"blocks": {"k","v"}} per layer, or
    {"blocks": {"latent"}} under MLA; a moe prefill adds "moe_pairs", the
    (token, held expert) pairs it computed."""
    if window is None:
        window = cfg.sliding_window
    x = constrain_batch(L.embed(params["embed"], batch["tokens"], cfg))
    aux = jnp.zeros((), jnp.float32)
    cache = {}
    t = cfg.arch_type

    if t == "encdec":
        x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
        enc = batch["enc_emb"].astype(x.dtype)
        enc = enc + L.sinusoidal_positions(enc.shape[1], cfg.d_model).astype(x.dtype)

        def enc_body(h, bp):
            a, _ = L.attention_forward(
                bp["attn"], L.rms_norm(h, bp["ln1"], cfg.norm_eps), cfg,
                causal=False, attn_impl=attn_impl)
            h = h + a
            h = h + L.mlp_forward(bp["mlp"], L.rms_norm(h, bp["ln2"], cfg.norm_eps), cfg)
            return h, None
        enc, _ = jax.lax.scan(_maybe_remat(enc_body, cfg), enc, params["enc"])
        enc = L.rms_norm(enc, params["enc_ln"], cfg.norm_eps)

        def dec_body(h, bp):
            a, kv = L.attention_forward(
                bp["attn"], L.rms_norm(h, bp["ln1"], cfg.norm_eps), cfg,
                attn_impl=attn_impl)
            h = h + a
            c, ckv = L.attention_forward(
                bp["cross"], L.rms_norm(h, bp["ln2"], cfg.norm_eps), cfg,
                causal=False, kv_src=enc)
            h = h + c
            h = h + L.mlp_forward(bp["mlp"], L.rms_norm(h, bp["ln3"], cfg.norm_eps), cfg)
            return h, ({"k": kv[0], "v": kv[1],
                        "ck": ckv[0], "cv": ckv[1]} if return_cache else None)
        x, dec_cache = jax.lax.scan(_maybe_remat(dec_body, cfg), x, params["blocks"])
        if return_cache:
            cache["blocks"] = dec_cache

    elif t in ("dense", "moe"):
        def rows(kv):
            if not return_cache:
                return None
            return {"latent": kv} if cfg.mla else {"k": kv[0], "v": kv[1]}

        def body(h, bp):
            h, kv = _dense_block(bp, h, cfg, window=window,
                                 attn_impl=attn_impl, collect=return_cache)
            return h, rows(kv)
        if t == "dense":
            x, kvs = jax.lax.scan(_maybe_remat(body, cfg), x, params["blocks"])
        else:
            if cfg.first_k_dense:
                x, kv0 = jax.lax.scan(_maybe_remat(body, cfg), x,
                                      params["dense_blocks"])

            def moe_body(h, bp):
                h, a, kv = _moe_block(bp, h, cfg, window=window,
                                      attn_impl=attn_impl,
                                      collect=return_cache,
                                      mask=batch.get("mask"))
                return h, (a, rows(kv))
            x, (auxs, kvs) = jax.lax.scan(_maybe_remat(moe_body, cfg), x,
                                          params["blocks"])
            if return_cache:
                cache["moe_pairs"] = auxs
                if cfg.first_k_dense:
                    kvs = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                       kv0, kvs)
            else:
                aux = auxs.sum()
        if return_cache:
            cache["blocks"] = kvs

    elif t == "ssm":
        def body(h, bp):
            h, hf = _ssm_block(bp, h, cfg, collect=return_cache)
            return h, hf
        x, hfs = jax.lax.scan(_maybe_remat(body, cfg), x, params["blocks"])
        if return_cache:
            cache["blocks"] = hfs

    elif t == "hybrid":
        plen, n_super, n_rem = _hybrid_counts(cfg)
        lw = cfg.hybrid.local_window

        def super_body(h, sp):
            states = []
            n_rec = sp["rec"]["ln1"].shape[0]
            for i in range(n_rec):
                bp = jax.tree.map(lambda a: a[i], sp["rec"])
                h, st = _rec_block(bp, h, cfg, collect=return_cache)
                states.append(st)
            h, kv = _dense_block(sp["attn"], h, cfg, window=lw,
                                 attn_impl=attn_impl, collect=return_cache)
            out = None
            if return_cache:
                out = {"rec": jnp.stack(states), "k": kv[0], "v": kv[1]}
            return h, out
        x, sc = jax.lax.scan(_maybe_remat(super_body, cfg), x, params["super"])
        if return_cache:
            cache["super"] = sc
        if n_rem:
            rems = []
            for i in range(n_rem):
                bp = jax.tree.map(lambda a: a[i], params["rem"])
                x, st = _rec_block(bp, x, cfg, collect=return_cache)
                rems.append(st)
            if return_cache:
                cache["rem"] = jnp.stack(rems)

    elif t == "vlm":
        per, n_super, n_rem = _vlm_counts(cfg)
        img = batch["img_emb"].astype(x.dtype)

        def super_body(h, sp):
            kvs = []
            for i in range(per - 1):
                bp = jax.tree.map(lambda a: a[i], sp["self"])
                h, kv = _dense_block(bp, h, cfg, window=window,
                                     attn_impl=attn_impl, collect=return_cache)
                kvs.append(kv)
            h, ckv = _cross_block(sp["cross"], h, img, cfg, collect=return_cache)
            out = None
            if return_cache:
                out = {"k": jnp.stack([kv[0] for kv in kvs]),
                       "v": jnp.stack([kv[1] for kv in kvs]),
                       "ck": ckv[0], "cv": ckv[1]}
            return h, out
        x, sc = jax.lax.scan(_maybe_remat(super_body, cfg), x, params["super"])
        if return_cache:
            cache["super"] = sc
        if n_rem:
            for i in range(n_rem):
                bp = jax.tree.map(lambda a: a[i], params["rem"])
                x, _ = _dense_block(bp, x, cfg, window=window, attn_impl=attn_impl)
    else:
        raise ValueError(t)

    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, aux, (cache if return_cache else None)


# ---------------------------------------------------------------------------
# Decode (one token, cached)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, seq_len: int,
               window: Optional[int] = None):
    """Decode-state pytree. Attention caches are (B, W, K, hd) ring buffers
    where W = min(window-or-sliding-window, seq_len)."""
    if window is None:
        window = cfg.sliding_window
    t = cfg.arch_type
    cd = cfg.dtype("compute")
    if cfg.mla or cfg.first_k_dense:
        raise ValueError("latent attention and leading dense layers decode "
                         "from the paged pool only (serving.decode)")
    if t in ("dense", "moe"):
        one = L.init_attn_cache(batch, cfg, seq_len, window)
        return {"blocks": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape).copy(), one)}
    if t == "ssm":
        one = S.init_ssm_cache(batch, cfg)
        return {"blocks": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape).copy(), one)}
    if t == "hybrid":
        plen, n_super, n_rem = _hybrid_counts(cfg)
        n_rec = sum(1 for x in cfg.hybrid.pattern if x == "rec")
        rec_one = R.init_rglru_cache(batch, cfg)
        attn_one = L.init_attn_cache(batch, cfg, seq_len, cfg.hybrid.local_window)
        sup = {"rec": jax.tree.map(
                   lambda a: jnp.broadcast_to(a, (n_super, n_rec) + a.shape).copy(),
                   rec_one),
               "attn": jax.tree.map(
                   lambda a: jnp.broadcast_to(a, (n_super,) + a.shape).copy(),
                   attn_one)}
        out = {"super": sup}
        if n_rem:
            out["rem"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_rem,) + a.shape).copy(), rec_one)
        return out
    if t == "vlm":
        per, n_super, n_rem = _vlm_counts(cfg)
        one = L.init_attn_cache(batch, cfg, seq_len, window)
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        img_kv = jnp.zeros((n_super, batch, cfg.num_image_tokens, kv, hd), dtype=cd)
        out = {"super": {
            "self": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_super, per - 1) + a.shape).copy(), one),
            "ck": img_kv, "cv": img_kv}}
        if n_rem:
            out["rem"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_rem,) + a.shape).copy(), one)
        return out
    if t == "encdec":
        one = L.init_attn_cache(batch, cfg, seq_len, None)
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cross = jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq, kv, hd), dtype=cd)
        return {"blocks": {
            "k": jnp.broadcast_to(one["k"], (cfg.num_layers,) + one["k"].shape).copy(),
            "v": jnp.broadcast_to(one["v"], (cfg.num_layers,) + one["v"].shape).copy(),
            "ck": cross, "cv": cross}}
    raise ValueError(t)


def decode_step(params, cache, tokens, pos, cfg: ArchConfig,
                window: Optional[int] = None):
    """tokens: (B,1) int32; pos: scalar int32. Returns (logits (B,1,V), cache)."""
    if window is None:
        window = cfg.sliding_window
    x = L.embed(params["embed"], tokens, cfg)
    t = cfg.arch_type

    if t in ("dense", "moe"):
        def body(h, xs):
            bp, c = xs
            a, nc = L.attention_decode(
                bp["attn"], L.rms_norm(h, bp["ln1"], cfg.norm_eps), c, pos, cfg,
                window=window)
            h = h + a
            h2 = L.rms_norm(h, bp["ln2"], cfg.norm_eps)
            if t == "dense":
                h = h + L.mlp_forward(bp["mlp"], h2, cfg)
            else:
                y, _ = M.moe_dropless(bp["moe"], h2, cfg)
                h = h + y
            return h, nc
        x, nc = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
        new_cache = {"blocks": nc}

    elif t == "ssm":
        def body(h, xs):
            bp, c = xs
            y, nc = S.ssm_decode(bp["ssm"], L.rms_norm(h, bp["ln"], cfg.norm_eps),
                                 c, cfg)
            return h + y, nc
        x, nc = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
        new_cache = {"blocks": nc}

    elif t == "hybrid":
        plen, n_super, n_rem = _hybrid_counts(cfg)
        lw = cfg.hybrid.local_window

        def body(h, xs):
            sp, c = xs
            nrec = []
            n_rec = sp["rec"]["ln1"].shape[0]
            for i in range(n_rec):
                bp = jax.tree.map(lambda a: a[i], sp["rec"])
                ci = jax.tree.map(lambda a: a[i], c["rec"])
                y, nci = R.rglru_decode(
                    bp["rec"], L.rms_norm(h, bp["ln1"], cfg.norm_eps), ci, cfg)
                h = h + y
                h = h + L.mlp_forward(bp["mlp"],
                                      L.rms_norm(h, bp["ln2"], cfg.norm_eps), cfg)
                nrec.append(nci)
            bp = sp["attn"]
            a, nattn = L.attention_decode(
                bp["attn"], L.rms_norm(h, bp["ln1"], cfg.norm_eps), c["attn"],
                pos, cfg, window=lw)
            h = h + a
            h = h + L.mlp_forward(bp["mlp"],
                                  L.rms_norm(h, bp["ln2"], cfg.norm_eps), cfg)
            nrec = jax.tree.map(lambda *xs: jnp.stack(xs), *nrec)
            return h, {"rec": nrec, "attn": nattn}
        x, nsup = jax.lax.scan(body, x, (params["super"], cache["super"]))
        new_cache = {"super": nsup}
        if n_rem:
            nrem = []
            for i in range(n_rem):
                bp = jax.tree.map(lambda a: a[i], params["rem"])
                ci = jax.tree.map(lambda a: a[i], cache["rem"])
                y, nci = R.rglru_decode(
                    bp["rec"], L.rms_norm(x, bp["ln1"], cfg.norm_eps), ci, cfg)
                x = x + y
                x = x + L.mlp_forward(bp["mlp"],
                                      L.rms_norm(x, bp["ln2"], cfg.norm_eps), cfg)
                nrem.append(nci)
            new_cache["rem"] = jax.tree.map(lambda *xs: jnp.stack(xs), *nrem)

    elif t == "vlm":
        per, n_super, n_rem = _vlm_counts(cfg)

        def body(h, xs):
            sp, c = xs
            nself = []
            for i in range(per - 1):
                bp = jax.tree.map(lambda a: a[i], sp["self"])
                ci = jax.tree.map(lambda a: a[i], c["self"])
                a, nci = L.attention_decode(
                    bp["attn"], L.rms_norm(h, bp["ln1"], cfg.norm_eps), ci, pos,
                    cfg, window=window)
                h = h + a
                h = h + L.mlp_forward(bp["mlp"],
                                      L.rms_norm(h, bp["ln2"], cfg.norm_eps), cfg)
                nself.append(nci)
            bp = sp["cross"]
            a, _ = L.attention_decode(
                bp["cross"], L.rms_norm(h, bp["ln1"], cfg.norm_eps), None, pos,
                cfg, kv_src_cache={"k": c["ck"], "v": c["cv"]})
            h = h + a
            h = h + L.mlp_forward(bp["mlp"],
                                  L.rms_norm(h, bp["ln2"], cfg.norm_eps), cfg)
            nself = jax.tree.map(lambda *xs: jnp.stack(xs), *nself)
            return h, {"self": nself, "ck": c["ck"], "cv": c["cv"]}
        x, nsup = jax.lax.scan(body, x, (params["super"], cache["super"]))
        new_cache = {"super": nsup}
        if n_rem:
            nrem = []
            for i in range(n_rem):
                bp = jax.tree.map(lambda a: a[i], params["rem"])
                ci = jax.tree.map(lambda a: a[i], cache["rem"])
                a, nci = L.attention_decode(
                    bp["attn"], L.rms_norm(x, bp["ln1"], cfg.norm_eps), ci, pos,
                    cfg, window=window)
                x = x + a
                x = x + L.mlp_forward(bp["mlp"],
                                      L.rms_norm(x, bp["ln2"], cfg.norm_eps), cfg)
                nrem.append(nci)
            new_cache["rem"] = jax.tree.map(lambda *xs: jnp.stack(xs), *nrem)

    elif t == "encdec":
        x = x + L.sinusoidal_positions(1, cfg.d_model).astype(x.dtype)

        def body(h, xs):
            bp, c = xs
            a, nself = L.attention_decode(
                bp["attn"], L.rms_norm(h, bp["ln1"], cfg.norm_eps),
                {"k": c["k"], "v": c["v"]}, pos, cfg)
            h = h + a
            cc, _ = L.attention_decode(
                bp["cross"], L.rms_norm(h, bp["ln2"], cfg.norm_eps), None, pos,
                cfg, kv_src_cache={"k": c["ck"], "v": c["cv"]})
            h = h + cc
            h = h + L.mlp_forward(bp["mlp"],
                                  L.rms_norm(h, bp["ln3"], cfg.norm_eps), cfg)
            return h, {"k": nself["k"], "v": nself["v"],
                       "ck": c["ck"], "cv": c["cv"]}
        x, nc = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
        new_cache = {"blocks": nc}
    else:
        raise ValueError(t)

    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, new_cache


def prefill(params, cache, tokens, cfg: ArchConfig,
            window: Optional[int] = None):
    """Batched prompt prefill: fill the KV/state cache for a whole (B, P)
    prompt in ONE jitted call and return the logits at the last prompt
    position (the first generation step's input).

    Internally a ``lax.scan`` of ``decode_step`` over prompt positions —
    cache-consistent for every arch family (ring buffers, SSM/RG-LRU
    states, cross-attention) with none of the per-token Python dispatch
    the old decode-the-prompt loop paid. Returns (logits (B,1,V), cache).
    """
    P = tokens.shape[1]
    if P == 1:
        return decode_step(params, cache, tokens, jnp.int32(0), cfg, window)

    def body(c, t):
        tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
        _, c = decode_step(params, c, tok, t, cfg, window)
        return c, None

    cache, _ = jax.lax.scan(body, cache, jnp.arange(P - 1, dtype=jnp.int32))
    return decode_step(params, cache, tokens[:, P - 1:P],
                       jnp.int32(P - 1), cfg, window)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def lm_loss(params, batch, cfg: ArchConfig, *, attn_impl: str = "xla",
            window: Optional[int] = None):
    """Next-token cross-entropy (+ MoE aux). batch needs "tokens","labels"."""
    logits, aux, _ = forward(params, batch, cfg, attn_impl=attn_impl,
                             window=window)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lab = batch["labels"]
    nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    return nll.mean() + aux
