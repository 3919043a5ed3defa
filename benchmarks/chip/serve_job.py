"""A serving cell: a configuration served by ``ContinuousServer`` under
one traffic mix, open loop, with arrivals on the traffic file's schedule
and token ids drawn from the seed.

Set-up makes the weights on the device, builds the server with the
traffic file's settings and warms it for the cell's prompt lengths. The
window is one ``ContinuousServer.run`` over every request due in
``seconds``: it ends when the last of them has finished. Then the
server's state is freed and the plain reference reads a sample of the
served requests.

TTFT and TPOT are read from the server's own records (``ServeReport``
and the ``serving.*`` series), so the harness holds those records to its
own clock: the host spans around each prefill call and decode step,
taken by the tracer the window installs, have to add up to what the
server recorded for the same calls (``clock_gap``).
"""
from __future__ import annotations

import gc
import importlib
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

import families
import loadgen
import models
from window import Window

#: the window records the server's host spans in every run (``clock_gap``)
SPANS = True
#: host time a call may spend between the server's clock and its span
CALL_SLACK_S = 1e-4


def _series_by_rid(registry, name: str) -> Dict[int, float]:
    s = registry.series(name)
    return {int(k): float(v) for k, v in zip(s.steps, s.values)}


def per_request(rep, registry) -> Dict[str, np.ndarray]:
    """TTFT (queue wait + the request's prefill call) and time per output
    token after the first, per finished request, on the server's clock."""
    pf = _series_by_rid(registry, "serving.prefill_s")
    dec = _series_by_rid(registry, "serving.decode_s")
    ttft, tpot = [], []
    for rid, qw, gen in zip(rep.rids, rep.queue_waits, rep.gen_counts):
        ttft.append(qw + pf[int(rid)])
        if gen > 1:
            tpot.append(dec[int(rid)] / (gen - 1))
    return {"ttft": np.asarray(ttft), "tpot": np.asarray(tpot)}


def clock_gap(registry, records) -> float:
    """How far the server's own times for its prefill calls and decode
    steps lie from the harness's spans around the same calls: the larger
    of the two relative gaps between the sums. The server reads its clock
    just outside each span, so ``CALL_SLACK_S`` a call is allowed for the
    host work between them. Each prefill call records one
    ``serving.prefill_s`` entry per admitted lane, in order."""
    pf_spans = [r for r in records if r.name == "serve.prefill"]
    dec_spans = [r for r in records if r.name == "serve.decode_step"]
    pf = registry.series("serving.prefill_s").values
    dec = registry.series("serving.decode_step_s").values
    if (not pf_spans or not dec_spans or len(dec) != len(dec_spans)
            or len(pf) != sum(int(r.attrs["lanes"]) for r in pf_spans)):
        return float("inf")
    calls, i = [], 0
    for r in pf_spans:
        calls.append(float(pf[i]))
        i += int(r.attrs["lanes"])
    gaps = []
    for prog, spans_ in ((calls, pf_spans), (dec, dec_spans)):
        span = sum(r.duration_s for r in spans_)
        off = abs(float(sum(prog)) - span) - CALL_SLACK_S * len(spans_)
        gaps.append(max(off, 0.0) / max(span, 1e-12))
    return max(gaps)


def sample_rids(rep, seed: int, n: int) -> List[int]:
    """``n`` finished requests drawn from the seed, the one with the most
    served tokens among them."""
    rids = [int(r) for r in rep.rids]
    longest = rids[int(np.argmax(rep.gen_counts))]
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng((seed, 7))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


#: reference sequences are padded to a multiple of this many tokens, so
#: its programs compile for a few lengths only
REF_PAD = 256


def reference_gaps(cfg: Dict, weights, seqs: List[np.ndarray],
                   plens: List[int], quant=None, ref_module=None) -> Dict:
    """Run the reference once over each prompt with its served tokens,
    one sequence at a time (padded at the end to a multiple of
    ``REF_PAD``, which a causal model does not see). Returns the widest
    gap of the served tokens under the float32 reference, and with
    ``quant`` the widest gap of the tokens the lower precision puts first
    at the same positions."""
    ref = ref_module or importlib.import_module(f"configs.{cfg['reference']}")
    served_gap = 0.0
    control_gap = 0.0
    n_tokens = 0
    for s, p in zip(seqs, plens):
        toks = np.zeros((1, -(-len(s) // REF_PAD) * REF_PAD), np.int32)
        toks[0, :len(s)] = s
        pos = np.arange(p - 1, len(s) - 1)            # positions that served
        hid = ref.final_hidden(weights, jnp.asarray(toks), cfg)[0, pos]
        served = np.asarray(s[p:])
        lg = np.asarray(ref.logits_at(weights, hid))
        best = lg.max(axis=1)
        served_gap = max(served_gap,
                         float((best - lg[np.arange(len(pos)), served]).max()))
        n_tokens += len(pos)
        del hid
        if quant:
            hq = ref.final_hidden(weights, jnp.asarray(toks), cfg,
                                  quant=quant)[0, pos]
            lq = np.asarray(ref.logits_at(weights, hq, quant=quant))
            first = lq.argmax(axis=1)
            control_gap = max(control_gap, float(
                (best - lg[np.arange(len(pos)), first]).max()))
    out = {"logit_gap": served_gap, "tokens": n_tokens}
    if quant:
        out["control_gap"] = control_gap
    return out


def build(cfg: Dict, traffic: Dict, seed: int):
    from repro.serving import ContinuousServer
    arch = families.of(cfg).program_config(cfg)
    weights = models.weights(cfg, models.key_from_seed(seed))
    srv = traffic["server"]
    server = ContinuousServer(arch, weights, slots=srv["slots"],
                              page_size=srv["page_size"],
                              max_seq=srv["max_seq"],
                              prefill_mode=srv["prefill_mode"])
    return arch, weights, server


def run(cell: Dict, cfg: Dict, traffic: Dict, seed: int, seconds: float,
        win: Window) -> Dict:
    from repro.obs.metrics import MetricRegistry
    arch, weights, server = build(cfg, traffic, seed)
    reqs = loadgen.requests(traffic, seed, seconds, arch.vocab_size)
    server.warmup([len(r.prompt) for r in reqs])
    server.reset(registry=MetricRegistry())
    win.setup_done()
    with win:
        rep = server.run(reqs)
    reg = server.registry
    pr = per_request(rep, reg)
    gap = clock_gap(reg, win.tracer.records())
    done = len(rep.rids)
    want = {r.rid: r.gen for r in reqs}
    short = int(sum(int(g) != want[int(rid)]
                    for rid, g in zip(rep.rids, rep.gen_counts)))
    out = {
        "attempted": len(reqs),
        "failed": len(reqs) - done + short,
        "e2e": {
            "serve_ttft_p90_ms": loadgen.percentile(pr["ttft"], 90) * 1e3,
            "serve_tpot_p90_ms": loadgen.percentile(pr["tpot"], 90) * 1e3,
            "serve_output_tokens_per_s": rep.total_tokens / rep.makespan,
        },
        "layer": {"report": rep, "registry": reg, "requests": reqs,
                  "per_request": pr, "makespan_s": rep.makespan,
                  "config": cfg},
        "info": {"requests": len(reqs), "finished": done,
                 "output_tokens": rep.total_tokens,
                 "makespan_s": rep.makespan, "wall_s": win.seconds,
                 "idle_skipped_s": rep.makespan - win.seconds,
                 "ttft_p50_ms": loadgen.percentile(pr["ttft"], 50) * 1e3,
                 "occupancy_mean": rep.occupancy_mean},
    }
    win.read_memory()
    server.pages = None
    del server
    gc.collect()
    by_rid = {r.rid: r for r in reqs}
    rids = sample_rids(rep, seed, traffic["check"]["requests"])
    seqs, plens = [], []
    for rid in rids:
        r = by_rid[rid]
        served = rep.tokens[rid]
        seqs.append(np.concatenate([r.prompt, served]).astype(np.int32))
        plens.append(len(r.prompt))
    g = reference_gaps(cfg, weights, seqs, plens)
    out["readings"] = {"logit_gap": g["logit_gap"], "clock_gap": gap}
    out["info"]["checked_tokens"] = g["tokens"]
    out["info"]["checked_requests"] = len(rids)
    return out
