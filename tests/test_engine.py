"""Unified execution engine: multi-device equivalence suite + plumbing.

The core contract (ISSUE acceptance): the shard_map-based grouped step on
8 forced host CPU devices BIT-matches the engine's single-device
reference (lax.map over the same (g, k) shard structure) at g in
{1, 2, 4}, for both update strategies, uniform and weighted
group_weights. Plus: strategy plugins, the Algorithm-1 Runner protocol,
trace replay through the engine, and telemetry feeding the cluster
calibration path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.async_sgd import make_grouped_train_step
from repro.core.auto_optimizer import algorithm1
from repro.core.compute_groups import group_batch_split
from repro.core.workload import (cnn_classify, init_state, make_runner,
                                 mlp_classify)
from repro.engine import (Engine, StrandedDevicesWarning, assign_buckets,
                          choose_data_parallel, device_batch_split)
from repro.engine.buckets import pack_bucket, unpack_bucket
from repro.engine.timing import Telemetry

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 host devices (tests/conftest.py forces them in tier-1)")


def _tree_bits_equal(a, b):
    return all(bool(jnp.all(x == y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _tree_close(a, b, rel=1e-6):
    """Per leaf, max |a - b| <= rel * max |a|: the spmd == reference pin
    where XLA fuses the same math differently in the two programs
    (docs/engine.md, "Known caveats")."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        scale = max(float(np.abs(x).max()), float(np.finfo(x.dtype).tiny))
        if float(np.abs(x - y).max()) > rel * scale:
            return False
    return True


def _run_pair(wl, *, strategy, g, weights=None, sizes=None, steps=3,
              lr=0.05, momentum=0.6, weight_decay=0.0, batch=32,
              **engine_kw):
    """(spmd_state, reference_state) after ``steps`` engine rounds."""
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batches = wl.sample_batches(jax.random.PRNGKey(1), steps, batch)
    kw = dict(strategy=strategy, num_groups=g, lr=lr, momentum=momentum,
              weight_decay=weight_decay, group_weights=weights,
              micro_sizes=sizes, head_filter=wl.head_filter, donate=False,
              **engine_kw)
    e_spmd = Engine(wl.loss_fn, exec_mode="spmd", **kw)
    e_ref = Engine(wl.loss_fn, exec_mode="reference", num_devices=8, **kw)
    ps, ms = params, mom
    pr, mr = params, mom
    ls = lr_ = None
    for t in range(steps):
        b = jax.tree.map(lambda x: x[t], batches)
        ps, ms, ls = e_spmd.step(ps, ms, b)
        pr, mr, lr_ = e_ref.step(pr, mr, b)
    return (ps, ms, ls), (pr, mr, lr_)


@needs8
@pytest.mark.parametrize("strategy", ["grouped-fused", "grouped-scan"])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_spmd_bitmatches_reference_uniform(strategy, g):
    """shard_map grouped step == lax.map single-device reference, bitwise,
    uniform group weights (MLP workload)."""
    wl = mlp_classify()
    (ps, ms, ls), (pr, mr, lr_) = _run_pair(wl, strategy=strategy, g=g)
    assert _tree_bits_equal(ps, pr), (strategy, g)
    assert _tree_bits_equal(ms, mr), (strategy, g)
    assert float(ls) == float(lr_), (strategy, g)


@needs8
@pytest.mark.parametrize("strategy", ["grouped-fused", "grouped-scan"])
@pytest.mark.parametrize("g", [2, 4])
def test_spmd_bitmatches_reference_weighted(strategy, g):
    """Same, with unequal heterogeneous group weights (share-weighted
    updates from a cluster allocation)."""
    wl = mlp_classify()
    weights = tuple(np.linspace(1.0, 2.0, g))
    (ps, ms, ls), (pr, mr, lr_) = _run_pair(wl, strategy=strategy, g=g,
                                            weights=weights)
    assert _tree_bits_equal(ps, pr), (strategy, g)
    assert _tree_bits_equal(ms, mr), (strategy, g)
    assert float(ls) == float(lr_), (strategy, g)


@needs8
@pytest.mark.parametrize("strategy", ["grouped-fused", "grouped-scan"])
def test_spmd_bitmatches_reference_cnn_head(strategy):
    """The paper's CNN workload with the merged-FC head filter: head
    params take the single averaged update, backbone the g stale updates —
    the same on the mesh and the reference, to within the scan update's
    FMA contraction (docs/engine.md, "Known caveats")."""
    wl = cnn_classify()
    (ps, ms, ls), (pr, mr, lr_) = _run_pair(wl, strategy=strategy, g=4,
                                            batch=16)
    assert _tree_close(ps, pr), strategy
    assert _tree_close(ms, mr), strategy
    assert float(ls) == float(lr_)


@needs8
def test_spmd_bitmatches_reference_sized_microbatches():
    """Ragged heterogeneous allocation: sized wrap-filled microbatches +
    weights, still bitwise across spmd/reference."""
    wl = mlp_classify()
    (ps, ms, _), (pr, mr, _) = _run_pair(
        wl, strategy="grouped-fused", g=2, weights=(0.625, 0.375),
        sizes=(20, 12), batch=32)
    assert _tree_bits_equal(ps, pr)
    assert _tree_bits_equal(ms, mr)


@needs8
@pytest.mark.parametrize("strategy", ["grouped-fused", "grouped-scan"])
def test_spmd_matches_reference_weight_decay_one_ulp(strategy):
    """With weight decay the update's multiply-add may FMA-contract
    differently between the two compiled programs (docs/engine.md):
    everything else pinned bitwise above, this case is pinned to <= 1 ulp
    of fp32."""
    wl = mlp_classify()
    (ps, _, _), (pr, _, _) = _run_pair(wl, strategy=strategy, g=2,
                                       weight_decay=1e-4)
    for a, b in zip(jax.tree.leaves(ps), jax.tree.leaves(pr)):
        np.testing.assert_array_almost_equal_nulp(np.asarray(a),
                                                  np.asarray(b), nulp=1)


@needs8
def test_spmd_bitmatches_reference_transformer():
    """Model-agnosticism: the reduced token-LM through the same engine,
    mesh vs reference, to within the final-norm gradient's reduction
    order (docs/engine.md, "Known caveats"). (Transformer backward is
    exactly the case where vmap-batched grads do NOT bit-match unbatched
    ones, which is what the shard-structured reference exists for.)"""
    from repro.configs import get_smoke_config
    from repro.models import transformer as T

    cfg = get_smoke_config("qwen2-7b")
    class WL:
        @staticmethod
        def init(key):
            return T.init_params(key, cfg)
        @staticmethod
        def loss_fn(p, b):
            return T.lm_loss(p, b, cfg)
        @staticmethod
        def sample_batches(key, steps, batch):
            k1, k2 = jax.random.split(key)
            return {"tokens": jax.random.randint(
                        k1, (steps, batch, 16), 0, cfg.vocab_size),
                    "labels": jax.random.randint(
                        k2, (steps, batch, 16), 0, cfg.vocab_size)}
        head_filter = None

    (ps, ms, ls), (pr, mr, lr_) = _run_pair(WL, strategy="grouped-fused",
                                            g=2, steps=2, batch=8)
    assert _tree_close(ps, pr)
    assert _tree_close(ms, mr)
    assert float(ls) == float(lr_)


def _mp_sharded(leaf):
    """True if ``leaf``'s committed sharding splits any dim over "mp"."""
    spec = getattr(getattr(leaf, "sharding", None), "spec", None) or ()
    return any(e == "mp" or (isinstance(e, tuple) and "mp" in e)
               for e in spec if e is not None)


@needs8
@pytest.mark.parametrize("strategy", ["grouped-fused", "grouped-scan"])
@pytest.mark.parametrize("g,mp", [(1, 1), (1, 2), (2, 1), (2, 2)])
def test_spmd_bitmatches_reference_model_parallel(strategy, g, mp):
    """ISSUE acceptance: with params/momentum STORED sharded over the
    third ("mp") mesh axis — gathered whole inside the step, grads
    sliced back to the local shard before the update — the grouped step
    still BIT-matches the unsharded single-device reference at
    (g, mp) in {1,2}x{1,2}, both update strategies. all_gather moves
    bits, the elementwise update commutes with slicing; nothing in the
    math may change."""
    wl = mlp_classify()
    (ps, ms, ls), (pr, mr, lr_) = _run_pair(wl, strategy=strategy, g=g,
                                            mp=mp)
    assert _tree_bits_equal(ps, pr), (strategy, g, mp)
    assert _tree_bits_equal(ms, mr), (strategy, g, mp)
    assert float(ls) == float(lr_), (strategy, g, mp)
    if mp > 1:
        # the storage really is model-parallel, not silently replicated
        assert any(_mp_sharded(l) for l in jax.tree.leaves(ps)), (strategy, g)
        assert any(_mp_sharded(l) for l in jax.tree.leaves(ms)), (strategy, g)


@needs8
def test_spmd_mp_explicit_rules_bitmatch():
    """User-supplied (path-regex, PartitionSpec) rules override the
    TENSOR_PREF/auto derivation — and stay bitwise-identical to the
    reference (rules choose WHERE bytes live, never what is computed)."""
    from jax.sharding import PartitionSpec as P
    wl = mlp_classify()
    rules = (((r"w1",), P(None, "mp")), ((r"b\d",), P()))
    (ps, ms, ls), (pr, mr, lr_) = _run_pair(
        wl, strategy="grouped-fused", g=2, mp=2, sharding_rules=rules)
    assert _tree_bits_equal(ps, pr)
    assert _tree_bits_equal(ms, mr)
    assert float(ls) == float(lr_)
    assert _mp_sharded(ps["w1"])


@needs8
@pytest.mark.parametrize("bucket_bytes", [1, 1 << 30])
def test_spmd_mp_bucketed_exchange_bitmatches(bucket_bytes):
    """Tentpole edge: the overlapped bucketed exchange buckets by LOCAL
    shard bytes when slabs are mp-sharded — tiny buckets (one local leaf
    per gather) and one huge slab both stay bitwise against the
    reference."""
    wl = mlp_classify()
    (ps, ms, ls), (pr, mr, lr_) = _run_pair(
        wl, strategy="grouped-fused", g=2, mp=2, bucket_bytes=bucket_bytes)
    assert _tree_bits_equal(ps, pr), bucket_bytes
    assert _tree_bits_equal(ms, mr), bucket_bytes
    assert float(ls) == float(lr_), bucket_bytes


@needs8
def test_engine_mp_validation():
    """mp plumbing guard-rails: vmap mode cannot shard storage; the
    device budget accounts for g*mp; describe() reports the 3-axis mesh."""
    wl = mlp_classify()
    with pytest.raises(ValueError, match="vmap"):
        Engine(wl.loss_fn, num_groups=2, mp=2, exec_mode="vmap")
    with pytest.raises(ValueError, match="mp"):
        Engine(wl.loss_fn, num_groups=2, mp=0)
    eng = Engine(wl.loss_fn, num_groups=2, mp=2, exec_mode="spmd",
                 donate=False)
    assert "2x2x2" in eng.describe(2, 8) or "mp" in eng.describe(2, 8)


@needs8
@pytest.mark.parametrize("strategy", ["grouped-fused", "grouped-scan"])
@pytest.mark.parametrize("bucket_bytes", [1, 1 << 30])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_spmd_bitmatches_reference_bucket_sizes(strategy, bucket_bytes, g):
    """The overlapped bucketed exchange is bitwise-invariant to the bucket
    plan: tiny buckets (one leaf per gather) and one huge slab both
    bit-match the reference — bucketing reorders independent gathers and
    packs bits unchanged, nothing more."""
    wl = mlp_classify()
    (ps, ms, ls), (pr, mr, lr_) = _run_pair(wl, strategy=strategy, g=g,
                                            bucket_bytes=bucket_bytes)
    assert _tree_bits_equal(ps, pr), (strategy, bucket_bytes, g)
    assert _tree_bits_equal(ms, mr), (strategy, bucket_bytes, g)
    assert float(ls) == float(lr_), (strategy, bucket_bytes, g)


@needs8
@pytest.mark.parametrize("g", [2, 4])
def test_spmd_losses_bitmatch_per_shard(g):
    """The single two-axis loss gather returns the same (g, k) per-shard
    loss board, bit for bit, as the reference's shard-ordered losses (the
    old nested data+group gather pair, collapsed to one collective)."""
    from repro.engine import (make_reference_grouped_step,
                              make_spmd_grouped_step)
    from repro.launch.mesh import make_group_mesh

    wl = mlp_classify()
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batch = jax.tree.map(lambda x: x[0],
                         wl.sample_batches(jax.random.PRNGKey(1), 1, 32))
    k = 8 // g
    gb = jax.tree.map(
        lambda t: t.reshape((g, t.shape[0] // g) + t.shape[1:]), batch)
    db = device_batch_split(gb, k)
    spmd = make_spmd_grouped_step(wl.loss_fn, make_group_mesh(g, k),
                                  lr=0.05, momentum=0.6)
    ref = make_reference_grouped_step(wl.loss_fn, g, k, lr=0.05,
                                      momentum=0.6)
    _, _, ls = jax.jit(spmd)(params, mom, db)
    _, _, lr_ = jax.jit(ref)(params, mom, db)
    assert ls.shape == (g, k) and lr_.shape == (g, k)
    assert np.asarray(ls).tobytes() == np.asarray(lr_).tobytes()


@needs8
@pytest.mark.parametrize("strategy", ["grouped-fused", "grouped-scan"])
def test_donating_step_hlo_has_no_param_copies(strategy):
    """Donation audit (the run-loop configuration): the compiled donating
    SPMD step aliases every params/momentum input to an output and
    contains no parameter-sized copy instruction — the in-place update
    actually happens in place."""
    import re

    wl = mlp_classify()
    eng = Engine(wl.loss_fn, strategy=strategy, num_groups=2, lr=0.05,
                 momentum=0.6, exec_mode="spmd")   # donate=True default
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batch = jax.tree.map(lambda x: x[0],
                         wl.sample_batches(jax.random.PRNGKey(1), 1, 32))
    built = eng._built_step(eng.strategy, g=2, lr=0.05, momentum=0.6,
                            per_group_batch=16)
    assert built.donating
    txt = built.fn.lower(params, mom, built.prepare(batch)) \
        .compile().as_text()
    n_state = len(jax.tree.leaves(params)) + len(jax.tree.leaves(mom))
    header = txt.splitlines()[0]
    assert "input_output_alias" in header
    aliased = re.findall(r"\{(\d+)\}: \(\d+, \{\}", header)
    assert len(aliased) >= n_state, header
    param_shapes = {tuple(l.shape) for l in jax.tree.leaves(params)}
    copies = []
    for line in txt.splitlines():
        m = re.search(r"= f32\[([\d,]*)\][^ ]* copy\(", line)
        if m:
            shp = (tuple(int(x) for x in m.group(1).split(","))
                   if m.group(1) else ())
            if shp in param_shapes:
                copies.append(line.strip())
    assert not copies, copies


def test_run_then_step_reuses_compile():
    """donate is not part of the compile-cache key: run() (donating),
    step() and profile() (both buffer-protected) on the same config share
    ONE built step instead of re-jitting."""
    wl = mlp_classify()
    eng = Engine(wl.loss_fn, num_groups=2, lr=0.05)   # donate=True default
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batches = wl.sample_batches(jax.random.PRNGKey(1), 3, 32)
    it = (jax.tree.map(lambda x: x[t], batches) for t in range(3))
    eng.run(params, mom, it, steps=3)
    assert len(eng._steps) == 1
    b0 = jax.tree.map(lambda x: x[0], batches)
    eng.step(params, mom, b0)
    eng.profile(params, mom, b0, warmup=1, iters=2)
    assert len(eng._steps) == 1      # still the single shared compile
    # the caller's buffers survived both protected entries
    assert np.isfinite(float(wl.loss_fn(params, b0)))


@needs8
@pytest.mark.parametrize("mp", [1, 2])
def test_spmd_run_compiles_the_step_once(mp):
    """Round two reuses round one's executable: the step's inputs are
    placed on the mesh before the first round, so round one's placed
    outputs look the same to jit (unplaced, a full-size step compiled
    twice on a four-chip v5e)."""
    wl = mlp_classify()
    eng = Engine(wl.loss_fn, strategy="grouped-fused", num_groups=2,
                 exec_mode="spmd", mp=mp, num_devices=4,
                 head_filter=wl.head_filter)
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batches = wl.sample_batches(jax.random.PRNGKey(1), 4, 32)
    it = (jax.tree.map(lambda x: x[t], batches) for t in range(4))
    eng.run(params, mom, it, steps=4)
    (built,) = eng._steps.values()
    assert built.mode == "spmd"
    assert built.fn._cache_size() == 1


def test_bucket_assignment_and_packing():
    """assign_buckets packs reverse flatten order (backward production
    order), splits on dtype/head class and size target; pack/unpack is a
    bit-exact round trip including a leading gather axis."""
    leaves = [jnp.zeros((32,)), jnp.zeros((4,)),
              jnp.ones((16, 32)), jnp.ones((32, 4))]
    flags = [False] * 4
    tiny = assign_buckets(leaves, flags, 1)
    assert [b.indices for b in tiny] == [(3,), (2,), (1,), (0,)]
    one = assign_buckets(leaves, flags, 1 << 30)
    assert [b.indices for b in one] == [(3, 2, 1, 0)]
    assert one[0].num_elements == sum(l.size for l in leaves)
    # 600-byte target: w2 (512 B), w1 (2048 B), then both biases
    mid = assign_buckets(leaves, flags, 600)
    assert [b.indices for b in mid] == [(3,), (2,), (1, 0)]
    # head leaves never share a slab with backbone leaves
    split = assign_buckets(leaves, [False, False, False, True], 1 << 30)
    assert [(b.indices, b.is_head) for b in split] == \
        [((3,), True), ((2, 1, 0), False)]
    # mixed dtypes split too
    leaves2 = [jnp.zeros((8,), jnp.float32), jnp.zeros((8,), jnp.bfloat16)]
    assert len(assign_buckets(leaves2, [False, False], 1 << 30)) == 2
    # pack -> unpack round trip, with and without a leading (g,) axis
    vals = [jax.random.normal(jax.random.PRNGKey(i), l.shape)
            for i, l in enumerate(leaves)]
    for b in mid:
        slab = pack_bucket(b, vals)
        back = unpack_bucket(b, slab)
        for i, arr in zip(b.indices, back):
            assert np.asarray(arr).tobytes() == np.asarray(vals[i]).tobytes()
        stacked = jnp.stack([slab, slab + 1.0])
        assert unpack_bucket(b, stacked)[0].shape == \
            (2,) + vals[b.indices[0]].shape
    with pytest.raises(ValueError, match="bucket_bytes"):
        assign_buckets(leaves, flags, 0)


def test_choose_data_parallel_warns_on_stranded_devices():
    """Silent k=1 fallback no longer silent: stranding device slots warns
    and lands in engine telemetry."""
    with pytest.warns(StrandedDevicesWarning, match="k=2 < 4"):
        assert choose_data_parallel(10, 4) == 2
    with pytest.warns(StrandedDevicesWarning, match="k=1 < 4"):
        assert choose_data_parallel(7, 4) == 1
    import warnings as W
    with W.catch_warnings():
        W.simplefilter("error")               # full mesh: no warning
        assert choose_data_parallel(16, 4) == 4
        assert choose_data_parallel(10, 4, warn=False) == 2
    if jax.device_count() >= 8:
        wl = mlp_classify()
        eng = Engine(wl.loss_fn, num_groups=2, lr=0.05, donate=False,
                     exec_mode="spmd")
        params = wl.init(jax.random.PRNGKey(0))
        mom = jax.tree.map(jnp.zeros_like, params)
        batch = jax.tree.map(lambda x: x[0],
                             wl.sample_batches(jax.random.PRNGKey(1), 1, 10))
        with pytest.warns(StrandedDevicesWarning):
            eng.step(params, mom, batch)      # per-group batch 5, slots 4
        assert any("stranded" in n for n in eng.telemetry.notes)
        assert "notes" in eng.telemetry.summary()


def test_vmap_mode_is_legacy_step():
    """exec_mode="vmap" reproduces make_grouped_train_step exactly (it IS
    the same step function behind the engine's batch preparation)."""
    wl = mlp_classify()
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batches = wl.sample_batches(jax.random.PRNGKey(1), 3, 32)
    eng = Engine(wl.loss_fn, strategy="grouped-fused", num_groups=4, lr=0.05,
                 momentum=0.9, exec_mode="vmap", donate=False)
    legacy = jax.jit(make_grouped_train_step(wl.loss_fn, num_groups=4,
                                             lr=0.05, momentum=0.9))
    pe, me = params, mom
    pl, ml = params, mom
    for t in range(3):
        b = jax.tree.map(lambda x: x[t], batches)
        pe, me, le = eng.step(pe, me, b)
        pl, ml, ll = legacy(pl, ml, group_batch_split(b, 4))
    assert _tree_bits_equal(pe, pl)
    assert _tree_bits_equal(me, ml)
    np.testing.assert_allclose(float(le), float(ll), rtol=1e-6)


def test_sync_strategy_pinned_to_g1():
    wl = mlp_classify()
    with pytest.raises(ValueError, match="pinned to g=1"):
        Engine(wl.loss_fn, strategy="sync", num_groups=4)
    runner = Engine(wl.loss_fn, strategy="sync",
                    sample_batches=wl.sample_batches, batch_size=8)
    with pytest.raises(ValueError, match="pinned to g=1"):
        runner((wl.init(jax.random.PRNGKey(0)), 0), g=2, mu=0.0, eta=0.05,
               steps=2, probe=True)
    eng = Engine(wl.loss_fn, strategy="sync", num_groups=1, lr=0.05,
                 momentum=0.6, donate=False)
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batches = wl.sample_batches(jax.random.PRNGKey(1), 3, 32)
    p = params
    for t in range(3):
        b = jax.tree.map(lambda x: x[t], batches)
        p, mom, loss = eng.step(p, mom, b)
    assert np.isfinite(float(loss))


def test_engine_is_algorithm1_runner():
    """make_runner returns an Engine; Algorithm 1 drives it end-to-end —
    no per-caller training loop left between them."""
    wl = mlp_classify()
    runner = make_runner(wl, seed=0)
    assert isinstance(runner, Engine)
    state = init_state(wl, seed=0)
    res = algorithm1(runner, state, n_devices=8, epochs=1, epoch_steps=40,
                     probe_steps=15, g0=2)
    assert res.losses[-10:].mean() < res.losses[:10].mean()


def test_engine_runner_probe_semantics():
    """Probe runs restart from the same checkpoint: state unchanged, same
    key schedule as the historical closure-based runner."""
    wl = mlp_classify()
    runner = make_runner(wl, seed=0)
    state = init_state(wl, seed=0)
    s1, l1 = runner(state, g=2, mu=0.3, eta=0.05, steps=10, probe=True)
    s2, l2 = runner(state, g=2, mu=0.3, eta=0.05, steps=10, probe=True)
    assert s1 is state and s2 is state
    np.testing.assert_array_equal(l1, l2)
    s3, _ = runner(state, g=2, mu=0.3, eta=0.05, steps=10, probe=False)
    assert s3[1] == 10


def test_grouped_runner_strategy_trains():
    """The deployable grouped step as the Runner substrate (the SPMD mesh
    engages automatically when enough devices are visible)."""
    wl = mlp_classify()
    runner = make_runner(wl, seed=0, strategy="grouped-fused")
    state = init_state(wl, seed=0)
    (final, t0), losses = runner(state, g=4, mu=0.3, eta=0.05, steps=30,
                                 probe=False)
    assert t0 == 30
    assert losses[-5:].mean() < losses[:5].mean()


def test_trace_replay_strategy_matches_direct_replay():
    """Engine(strategy="trace-replay") == repro.exec.replay_trace on the
    same trace/batches — _replay_main's old body, now a strategy."""
    from repro.core import queue_sim
    from repro.exec import replay_trace

    wl = mlp_classify()
    params = wl.init(jax.random.PRNGKey(0))
    T = 12
    _, trace = queue_sim.simulate(g=3, t_conv=1.0, t_fc=0.05, iters=T,
                                  exponential=True, seed=3, return_trace=True)
    batches = wl.sample_batches(jax.random.PRNGKey(1), T, wl.batch_size)
    eng = Engine(wl.loss_fn, strategy="trace-replay", trace=trace, lr=0.05,
                 momentum=0.3, replay_impl="scan")
    it = (jax.tree.map(lambda x: x[t], batches) for t in range(T))
    pf, _, losses = eng.run(params, None, it, steps=T)
    pf2, losses2, _ = replay_trace(wl.loss_fn, params, batches, trace,
                                   lr=0.05, momentum=0.3, impl="scan")
    assert _tree_bits_equal(pf, pf2)
    np.testing.assert_allclose(losses, np.asarray(losses2), rtol=1e-6)


def test_trace_replay_requires_trace_and_rejects_runner():
    wl = mlp_classify()
    eng = Engine(wl.loss_fn, strategy="trace-replay")
    with pytest.raises(ValueError, match="trace"):
        eng.run(wl.init(jax.random.PRNGKey(0)), None, iter([]), steps=4)
    with pytest.raises(ValueError, match="Runner"):
        eng((None, 0), g=1, mu=0.0, eta=0.1, steps=1, probe=True)


def test_telemetry_feeds_cluster_calibration():
    """Engine telemetry -> black-box DeviceSpec throughput (the planner
    calibration path) without a separate probe run."""
    from repro.cluster import DeviceSpec, spec_from_telemetry

    wl = mlp_classify()
    eng = Engine(wl.loss_fn, num_groups=2, lr=0.05, donate=False)
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    batches = wl.sample_batches(jax.random.PRNGKey(1), 4, 32)
    it = (jax.tree.map(lambda x: x[t], batches) for t in range(4))
    eng.run(params, mom, it, steps=4)
    assert len(eng.telemetry) == 4
    assert eng.telemetry.median_step_s() > 0
    spec = spec_from_telemetry(
        DeviceSpec("probe", "cpu", peak_flops=1e12, mem_bw=1e11,
                   net_bw=1e9),
        eng.telemetry, batch_size=32)
    assert spec.throughput == eng.telemetry.throughput(32)
    assert spec.predict_throughput() == spec.throughput
    # profile(): the cluster probe contract against the engine's own step
    b0 = jax.tree.map(lambda x: x[0], batches)
    thr = eng.profile(params, mom, b0, warmup=1, iters=3)
    assert thr > 0


def test_telemetry_stats():
    t = Telemetry(skip=1)
    with pytest.raises(ValueError):
        t.median_step_s()
    for s in (5.0, 0.2, 0.4, 0.3):     # first (compile) step skipped
        t.record(step_s=s, data_s=0.01)
    assert t.median_step_s() == 0.3
    assert abs(t.throughput(30) - 100.0) < 1e-9
    s = t.summary(batch_size=30)
    assert s["steps"] == 4 and "examples_per_s" in s


def test_choose_data_parallel_and_device_split():
    assert choose_data_parallel(16, 4) == 4
    # largest divisor of 10 <= 4 is 2; 7 forces k=1 (warning behaviour is
    # pinned by test_choose_data_parallel_warns_on_stranded_devices)
    assert choose_data_parallel(10, 4, warn=False) == 2
    assert choose_data_parallel(7, 4, warn=False) == 1
    assert choose_data_parallel(0, 4) == 1
    gb = {"x": jnp.zeros((2, 6, 3))}
    db = device_batch_split(gb, 2)
    assert db["x"].shape == (2, 2, 3, 3)
    with pytest.raises(ValueError, match="not divisible"):
        device_batch_split(gb, 4)


def test_reference_mode_needs_no_devices():
    """The reference twin runs on one device regardless of the visible
    pool — num_devices only shapes the (g, k) structure it mirrors."""
    wl = mlp_classify()
    eng = Engine(wl.loss_fn, num_groups=4, lr=0.05, exec_mode="reference",
                 num_devices=1)
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    b = jax.tree.map(lambda x: x[0],
                     wl.sample_batches(jax.random.PRNGKey(1), 1, 32))
    _, _, loss = eng.step(params, mom, b)
    assert np.isfinite(float(loss))
    built = next(iter(eng._steps.values()))
    assert built.mode == "reference" and built.k == 1


def test_step_never_donates_caller_buffers():
    """Engine.step must leave the caller's arrays alive even with the
    engine's donating run-loop configuration (donate=True default)."""
    wl = mlp_classify()
    eng = Engine(wl.loss_fn, num_groups=2, lr=0.05)   # donate=True default
    params = wl.init(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)
    b = jax.tree.map(lambda x: x[0],
                     wl.sample_batches(jax.random.PRNGKey(1), 1, 32))
    eng.step(params, mom, b)
    # original buffers still usable after the step
    assert np.isfinite(float(wl.loss_fn(params, b)))
    # and run() protects them too (copy-in before its donating loop)
    it = (jax.tree.map(lambda x: x[t],
                       wl.sample_batches(jax.random.PRNGKey(2), 3, 32))
          for t in range(3))
    eng.run(params, mom, it, steps=3)
    assert np.isfinite(float(wl.loss_fn(params, b)))


def test_engine_describe_and_spec():
    wl = mlp_classify()
    eng = Engine(wl.loss_fn, num_groups=4)
    spec = eng.group_spec()
    assert spec.staleness == 3
    d = eng.describe(4, 8)
    assert "g=4" in d and "S=3" in d
