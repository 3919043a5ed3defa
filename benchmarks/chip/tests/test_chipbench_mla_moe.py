"""DeepSeek-V2-Lite's mechanisms on the CPU stand-in ``tiny-mla-moe``
against the plain reference: YaRN at the published values, absorbed
against decompressed latent attention, the expert layer's shares against
the uncut layer, no dropped token, prefill then paged decode through
``ContinuousServer`` against the reference's full forward, the server's
records of its expert work, and the family's counts."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
import models
from families import mla_moe

ref = importlib.import_module("configs.reference_mla_moe")


@pytest.fixture(scope="module")
def tiny():
    cfg = models.load_config("tiny-mla-moe")
    return cfg, mla_moe.program_config(cfg)


@pytest.fixture(scope="module")
def weights(tiny):
    return models.weights(tiny[0], models.key_from_seed(2**31 + 3))


def _f32(arch):
    return dataclasses.replace(arch, param_dtype="float32",
                               compute_dtype="float32")


def test_yarn_and_softmax_scale_at_the_published_values():
    from repro.models import layers as L
    from repro.models import mla
    cfg = models.load_config("deepseek-v2-lite-ep4")
    arch = mla_moe.program_config(cfg)
    assert L.yarn_correction_range(arch.yarn, 64, 1e4) == (10, 23)
    assert mla.softmax_scale(arch) == pytest.approx(0.114721, abs=5e-7)
    assert L.yarn_mscale(40, 0.707) == pytest.approx(1.26080, abs=5e-6)
    inv_freq, cs, scale, lohi = ref.yarn(cfg)
    assert lohi == (10, 23) and cs == 1.0
    assert scale == pytest.approx(mla.softmax_scale(arch), rel=1e-12)
    # the program's rotation of a unit pair is the reference's frequency
    pos = jnp.arange(3000)[None, :]
    x = jnp.zeros((1, 3000, 1, 64)).at[..., 0::2].set(1.0)
    got = L.rope(x, pos, 1e4, arch.yarn)[0, :, 0, 1::2]      # sin(p * f_i)
    want = np.sin(np.arange(3000)[:, None] * inv_freq.astype(np.float64))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-3)


def test_absorbed_decode_equals_decompressed_attention(tiny):
    from repro.models import mla
    _, arch = tiny
    arch = _f32(arch)
    p = mla.init_mla(jax.random.PRNGKey(0), arch)
    p["kv_norm"] = jax.random.normal(jax.random.PRNGKey(2), (64,)) * 0.1
    B, S = 2, 40
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, arch.d_model))
    y, rows = jax.jit(lambda p, x: mla.mla_forward(p, x, arch))(p, x)
    assert rows.shape == (B, S, arch.mla.latent_dim)
    decode = jax.jit(lambda p, x, r, v, t: mla.mla_decode(p, x, r, v, t, arch))
    for t in (0, 17, S - 1):
        valid = jnp.broadcast_to(jnp.arange(S) <= t, (B, S))
        yd = decode(p, x[:, t:t + 1], rows, valid, jnp.full((B,), t))
        np.testing.assert_allclose(np.asarray(yd[:, 0]),
                                   np.asarray(y[:, t]), rtol=2e-5, atol=2e-5)


def _moe_params(arch, key):
    from repro.models import moe
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        moe.init_moe(key, arch))


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """Four chips' shares of the 16 experts: their routed parts, with the
    shared expert counted once, are the reference's whole layer. A chip
    holds the first experts of the router it is given, so share s sees
    the router's columns with experts 4s .. 4s + 3 put first."""
    from repro.models import moe
    cfg, arch = tiny
    arch = _f32(arch)
    whole = dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, num_experts=16))
    p = _moe_params(whole, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 20, arch.d_model))
    sp = p["shared"]
    shared = moe._swiglu(x, sp["w_gate"], sp["w_up"], sp["w_down"],
                         jnp.float32)
    routed = 0.0
    for first in (0, 4, 8, 12):
        sl = np.arange(first, first + 4)
        order = np.concatenate([sl, np.setdiff1d(np.arange(16), sl)])
        ps = dict(p, router=p["router"][:, order], w_gate=p["w_gate"][sl],
                  w_up=p["w_up"][sl], w_down=p["w_down"][sl])
        y = jax.jit(lambda p, x: moe.moe_dropless(p, x, arch)[0])(ps, x)
        routed = routed + (y - shared)
    uncut = ref._experts(p, x, dict(cfg, n_routed_experts=16), None)
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(uncut), rtol=1e-4, atol=1e-4)


def test_a_batch_routed_to_one_held_expert_drops_nothing(tiny):
    from repro.models import moe
    _, arch = tiny
    arch = _f32(arch)
    p = _moe_params(arch, jax.random.PRNGKey(5))
    d = arch.d_model
    # every token's largest logit is expert 2's; the other five chosen
    # lie outside the share held here (experts 0-3)
    router = jnp.zeros((d, 16)).at[0, 2].set(50.0)
    router = router.at[0, 4:9].set(jnp.arange(5.0) + 1.0)
    p["router"] = router
    n = 1024                       # far past any capacity a dispatch keeps
    x = jax.random.normal(jax.random.PRNGKey(6), (n, d)) * 0.1
    x = x.at[:, 0].set(1.0)
    y, (hit, pairs) = jax.jit(lambda p, x: moe.moe_dropless(p, x, arch))(p, x)
    assert int(hit) == 1 and int(pairs) == n
    probs = jax.nn.softmax(x @ router, -1)
    ffn = moe._swiglu(x, p["w_gate"][2], p["w_up"][2], p["w_down"][2],
                      jnp.float32)
    sp = p["shared"]
    want = (probs[:, 2:3] * ffn
            + moe._swiglu(x, sp["w_gate"], sp["w_up"], sp["w_down"],
                          jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_prefill_then_paged_decode_matches_the_reference(tiny, weights):
    """Parallel prefill and paged decode through ContinuousServer, then
    the logits of every decode step against the reference's full forward
    over the served sequence."""
    from repro.serving import ContinuousServer, paged_decode_step
    cfg, arch = tiny
    srv = ContinuousServer(arch, weights, slots=2, page_size=8, max_seq=64,
                           prefill_mode="parallel")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, size=n).astype(np.int32) for n in (21, 9)]
    S, Pb = 2, 32
    padded = np.zeros((S, Pb), np.int32)
    for s, pr in enumerate(prompts):
        srv.alloc.ensure(s, 64)
        padded[s, :len(pr)] = pr
    table = jnp.asarray(srv.alloc.tables)
    plens = np.array([len(p) for p in prompts], np.int32)
    pages, (toks, _) = srv._prefill_fn(Pb)(
        srv.params, srv.pages, table, jnp.asarray(padded),
        jnp.asarray(plens), jnp.ones(S, bool))
    seqs = [list(p) + [int(toks[len(p) - 1, s])]
            for s, p in enumerate(prompts)]
    got = [[] for _ in range(S)]
    step = jax.jit(lambda *a: paged_decode_step(*a, arch))
    for _ in range(6):
        tok = jnp.asarray([[q[-1]] for q in seqs], jnp.int32)
        pos = jnp.asarray([len(q) - 1 for q in seqs], jnp.int32)
        logits, pages = step(srv.params, pages, table, tok, pos,
                             jnp.ones(S, bool))
        for s in range(S):
            got[s].append(np.asarray(logits[s, 0]))
            seqs[s].append(int(np.argmax(logits[s, 0])))
    for s in range(S):
        seq = jnp.asarray(seqs[s][:-1])[None, :]
        hid = ref.final_hidden(weights, seq, cfg)[0]
        want = np.asarray(ref.logits_at(weights, hid))[-6:]
        gap = np.abs(np.stack(got[s]) - want).max()
        assert gap < 0.05 * np.abs(want).max(), (s, gap)
        # the served token is within bf16 rounding of the reference's best
        served = np.asarray(seqs[s][-6:])
        lag = want.max(1) - want[np.arange(6), served]
        assert lag.max() < 0.05, lag


def test_the_server_counts_its_expert_work(tiny, weights):
    """The expert work is kept per decode step and per prefill call, in
    bounds every step; the dense family's server keeps none of it."""
    import loadgen
    from cpu_cells import cpu_traffic
    from repro.serving import ContinuousServer
    cfg, arch = tiny
    traffic = cpu_traffic(loadgen.load_traffic("serve.chat.dsv2lite"))
    server = ContinuousServer(arch, weights, slots=4, page_size=16,
                              max_seq=256, prefill_mode="parallel")
    reqs = loadgen.requests(traffic, 2**31 + 7, 0.5, arch.vocab_size)
    rep = server.run(reqs)
    reg = server.registry
    series = lambda n: np.asarray(reg.series(n).values)
    occ = series("serving.occupancy")
    hit, pairs = (series("serving.moe_decode_experts_hit"),
                  series("serving.moe_decode_pairs"))
    live = series("serving.decode_live_tokens")
    assert len(hit) == len(pairs) == len(live) == len(
        series("serving.decode_step_s")) > 0
    layers = arch.num_layers - arch.first_k_dense
    assert (hit > 0).all() and (hit <= layers * arch.moe.num_experts).all()
    assert (hit <= pairs).all()
    assert (pairs <= occ * layers * arch.moe.top_k).all()
    # every decode step reads, per active slot, its position + 1 rows
    assert (live > occ).all()
    assert live.sum() >= sum(int(g) - 1 for g in rep.gen_counts)
    # each prefill call's pairs, and its prompts under its index
    ppairs = series("serving.moe_prefill_pairs")
    lens = reg.series("serving.prefill_call_prompt_tokens")
    assert sorted(lens.values) == sorted(len(r.prompt) for r in reqs)
    assert set(lens.steps) == set(range(len(ppairs)))
    for call, n in enumerate(ppairs):
        tokens = sum(v for v, c in zip(lens.values, lens.steps) if c == call)
        assert 0 < n <= tokens * layers * arch.moe.top_k

    dense = models.load_config("tiny-lm")
    dsrv = ContinuousServer(families.of(dense).program_config(dense),
                            slots=2, page_size=16, max_seq=64)
    dsrv.run([])
    assert not any(n in dsrv.registry.names() for n in (
        "serving.moe_decode_experts_hit", "serving.moe_decode_pairs",
        "serving.moe_prefill_pairs", "serving.decode_live_tokens",
        "serving.prefill_call_prompt_tokens"))


def test_parameters_and_weight_tree_match_the_program(tiny):
    from repro.models import transformer as T
    cfg = models.load_config("deepseek-v2-lite-ep4")
    assert families.of(cfg) is mla_moe
    assert mla_moe.params(cfg) == 4_910_345_728
    tcfg, arch = tiny
    ours = jax.eval_shape(mla_moe.weights_fn(tcfg), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: T.init_params(k, arch),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert sum(x.size for x in jax.tree.leaves(ours)) == mla_moe.params(tcfg)


def test_counts_from_the_published_shapes():
    cfg = models.load_config("deepseek-v2-lite-ep4")
    c = mla_moe._counts(cfg)
    assert c["attn"] == 27 * 13_762_560
    assert c["dense"] == 67_239_936 and c["expert"] == 8_650_752
    assert c["shared"] == 26 * 17_301_504 and c["router"] == 26 * 131_072
    # 6 of 64 experts, 16 held here: 1.5 routed experts a token and layer
    f = mla_moe.forward_flops_per_token(cfg, context=0)
    assert f == 2 * (c["attn"] + c["dense"] + c["router"] + c["shared"]
                     + c["head"] + 26 * 1.5 * c["expert"])
    # one live token adds its 576-wide latent row in all 27 layers
    w = mla_moe.decode_bytes(cfg, 0, 0)
    assert mla_moe.decode_bytes(cfg, 1, 0) - w == 27 * 576 * 2
    assert mla_moe.decode_bytes(cfg, 0, 1) - w == 2 * 8_650_752
    assert mla_moe.prefill_flops(cfg, [3], 0) == pytest.approx(
        3 * (f - 26 * 1.5 * 2 * c["expert"])
        + 6 * 2 * 27 * 16 * (192 + 128))


def _ctx(cfg, layer, ops, spans):
    import layer_context
    import peaks
    import trace_reduce
    red = trace_reduce.reduce({0: ops}, (10.0, 20.0))
    return layer_context.Context(
        cell={"chips": 1}, cfg=cfg, traffic={}, layer=layer,
        device={"kind": "TPU v5 lite"}, peak=peaks.peak("TPU v5 lite"),
        reduction=red, spans=list(spans), planes=[])


def _reader(name):
    import run
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read


def test_decode_hbm_share_reads_the_counters():
    """Bytes of each step the profile holds over the busy time of the
    same steps, ops the trace places before a step's span included; a
    step past the profile's end and spans before the window are left
    out."""
    from repro.obs.metrics import MetricRegistry
    cfg = models.load_config("deepseek-v2-lite-ep4")
    reg = MetricRegistry()
    # device ops 1 ms ahead of their spans; the third step's are lost
    ops = [("fusion.0", 10.999, 11.009), ("fusion.1", 11.999, 12.011),
           ("fusion.2", 12.5, 12.9)]
    spans = [("serve.decode_step", 1.0, 1.01, 0),       # before the window
             ("serve.decode_step", 11.0, 11.012, 0),
             ("serve.decode_step", 12.0, 12.013, 0),
             ("serve.prefill", 12.45, 12.95, 0),
             ("serve.decode_step", 14.0, 14.012, 0)]
    read = _reader("serve_decode_hbm_pct.dsv2lite")
    layer = {"registry": reg}
    assert read(_ctx(cfg, layer, ops, spans)) is None    # no series
    for hit, live in ((130, 3000), (104, 2000), (78, 1000)):
        reg.series("serving.moe_decode_experts_hit").append(hit)
        reg.series("serving.decode_live_tokens").append(live)
    want = (100 * (mla_moe.decode_bytes(cfg, 3000, 130)
                   + mla_moe.decode_bytes(cfg, 2000, 104))
            / ((0.010 + 0.012) * 819e9))
    assert read(_ctx(cfg, layer, ops, spans)) == pytest.approx(want,
                                                              rel=1e-9)
    # series that do not follow the spans one to one read nothing
    assert read(_ctx(cfg, layer, ops, spans[:-1])) is None


def test_prefill_mfu_reads_the_routed_pairs():
    """FLOPs of each prefill call the profile holds, from its own prompts
    and routed pairs, over the busy time of the same calls."""
    from repro.obs.metrics import MetricRegistry
    cfg = models.load_config("deepseek-v2-lite-ep4")
    reg = MetricRegistry()
    layer = {"registry": reg}
    ops = [("fusion.0", 10.85, 11.0), ("fusion.1", 11.2, 11.21),
           ("fusion.2", 11.88, 11.93)]
    spans = [("serve.prefill", 10.9, 11.1, 0),
             ("serve.decode_step", 11.2, 11.22, 0),
             ("serve.prefill", 11.9, 12.1, 0),
             ("serve.prefill", 14.0, 14.2, 0)]       # past the profile
    read = _reader("serve_prefill_mfu_pct.dsv2lite")
    assert read(_ctx(cfg, layer, ops, spans)) is None    # no series
    calls = (([700, 1300], 2000 * 26), ([500], 500 * 24), ([900], 900 * 20))
    for call, (lens, pairs) in enumerate(calls):
        reg.series("serving.moe_prefill_pairs").append(pairs, step=call)
        for n in lens:
            reg.series("serving.prefill_call_prompt_tokens").append(
                n, step=call)
    want = (100 * (mla_moe.prefill_flops(cfg, *calls[0])
                   + mla_moe.prefill_flops(cfg, *calls[1]))
            / ((0.15 + 0.05) * 197e12))
    assert read(_ctx(cfg, layer, ops, spans)) == pytest.approx(want,
                                                              rel=1e-9)
