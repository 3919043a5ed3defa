"""Compile-only checks for a described TPU v5e (``v5e:2x2``) at real
widths: the Pallas kernels of the main paths and the phi4-mini paged
decode step must pass the TPU compiler, with each kernel present as a
compiled custom call (not the interpreter's unrolled ops).

Nothing runs: the TPU compiler works on a described topology, with
arguments given as shapes placed on its first chip. The backend stays
the CPU, so these tests steer the one interpret rule
(``repro.kernels.interpret_mode``) to "compile" themselves. The topology
is described inside a fixture — never at import — because only one
process at a time may load the TPU library; every test of that kind
lives in this one file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compile_for_tpu(monkeypatch):
    """Kernels compile (no interpreter) and nothing touches the persistent
    compile cache: a TPU entry written here could not be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", old)


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_update_compiles(one_chip):
    from repro.kernels.fused_update.ops import fused_update
    from repro.optim.closed_form import grouped_coeffs
    c = grouped_coeffs(4, lr=0.01, momentum=0.9)
    f = jax.jit(lambda w, v, g: fused_update(w, v, g, coeffs=c,
                                             impl="pallas"))
    s = (4096, 9216)
    compiled = f.lower(_spec(one_chip, s, jnp.float32),
                       _spec(one_chip, s, jnp.float32),
                       _spec(one_chip, (4,) + s, jnp.float32)).compile()
    _assert_kernel(compiled)


def test_paged_attention_compiles(one_chip):
    """phi4-mini's decode heads: K=8 kv heads, G=3, hd=128, bf16 pages
    of 16 tokens (the 16-row bf16 sublane tile)."""
    from repro.kernels.paged_attention.ops import paged_attention
    B, K, G, hd, page, n_pages = 8, 8, 3, 128, 16, 32
    P = 1 + B * n_pages
    compiled = paged_attention.lower(
        _spec(one_chip, (B, 1, K * G, hd), jnp.bfloat16),
        _spec(one_chip, (P, page, K, hd), jnp.bfloat16),
        _spec(one_chip, (P, page, K, hd), jnp.bfloat16),
        _spec(one_chip, (B, n_pages), jnp.int32),
        _spec(one_chip, (B,), jnp.int32)).compile()
    _assert_kernel(compiled)


def test_flash_attention_compiles(one_chip):
    """S=2048 with 24 query / 8 kv heads, per-row offsets prefetched."""
    from repro.kernels.flash_attention.ops import flash_attention
    B, S, H, K, hd = 1, 2048, 24, 8, 128
    f = jax.jit(lambda q, k, v, off: flash_attention(q, k, v, q_offsets=off))
    compiled = f.lower(_spec(one_chip, (B, S, H, hd), jnp.bfloat16),
                       _spec(one_chip, (B, S, K, hd), jnp.bfloat16),
                       _spec(one_chip, (B, S, K, hd), jnp.bfloat16),
                       _spec(one_chip, (B,), jnp.int32)).compile()
    _assert_kernel(compiled)


def _compile_phi4_decode_step(one_chip, *, slots, max_seq, donate=False,
                              **step_kw):
    """phi4-mini's serving decode step at full width, 2 layers, bf16
    weights, page 16, compiled for the described chip. Returns the
    compiled step and the pool's shape."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import PagedCacheSpec, init_pages, paged_decode_step
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b"), num_layers=2,
                              param_dtype="bfloat16")
    spec = PagedCacheSpec.for_config(cfg, num_slots=slots, page_size=16,
                                     max_seq=max_seq)
    place = lambda t: jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), t)
    params = place(jax.eval_shape(lambda k: T.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
    pages = place(jax.eval_shape(lambda: init_pages(spec)))
    f = jax.jit(lambda p, pg, tbl, tok, pos, act: paged_decode_step(
        p, pg, tbl, tok, pos, act, cfg, **step_kw),
        donate_argnums=(1,) if donate else ())
    compiled = f.lower(params, pages,
                       _spec(one_chip, (slots, spec.pages_per_slot),
                             jnp.int32),
                       _spec(one_chip, (slots, 1), jnp.int32),
                       _spec(one_chip, (slots,), jnp.int32),
                       _spec(one_chip, (slots,), jnp.bool_)).compile()
    return compiled, pages["k"].shape


def test_phi4_paged_decode_step_compiles(one_chip):
    """The serving decode step of phi4-mini at full width, 2 layers, bf16
    weights, 8 slots of 512 tokens, through the paged kernel."""
    compiled, _ = _compile_phi4_decode_step(one_chip, slots=8, max_seq=512,
                                            attn_impl="pallas")
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    # 2 layers of bf16 weights + pools fit one 16 GB chip with room
    assert mem.argument_size_in_bytes < 4 << 30


def test_phi4_xla_decode_step_writes_pool_in_place(one_chip):
    """The chat cell's decode step (``attn_impl="xla"``, one gather rung,
    the pool donated as in ``ContinuousServer._step_fn``), 4 slots of
    3,072 tokens: no instruction copies or slices out the whole pool or
    one layer's pool, and no pool-sized temporary is left — the new rows
    are scattered into the donated buffer in place."""
    compiled, pool = _compile_phi4_decode_step(
        one_chip, slots=4, max_seq=3072, donate=True, attn_impl="xla",
        gather_pages=128)                            # (L, P, page, K, hd)
    layer = pool[1:]
    layer_bytes = 2 * int(np.prod(layer))           # bf16
    shapes = {",".join(map(str, s)) for s in (pool, layer, (1,) + layer)}
    moves = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]+)\]\S* "
                     r"(copy|dynamic-slice|dynamic-update-slice)\(", line)
        if m and m.group(1) in shapes:
            moves.append(line.strip()[:120])
    assert not moves, moves
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes, mem.temp_size_in_bytes


def _dsv2_lite_share():
    """DeepSeek-V2-Lite at its published widths, 16 of 64 experts held."""
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnConfig
    return ArchConfig(
        name="dsv2-lite-ep4", arch_type="moe", num_layers=27, d_model=2048,
        num_heads=16, num_kv_heads=16, d_ff=10944, vocab_size=102400,
        norm_eps=1e-6, first_k_dense=1, param_dtype="bfloat16",
        moe=MoEConfig(num_experts=16, top_k=6, d_ff_expert=1408,
                      num_shared_experts=2, router_experts=64,
                      norm_topk_prob=False),
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        yarn=YarnConfig(factor=40, original_max_position_embeddings=4096,
                        mscale=0.707, mscale_all_dim=0.707))


@pytest.mark.parametrize("tokens", [4, 512])
def test_dsv2_lite_expert_layer_compiles_to_one_grouped_matmul_kernel(
        one_chip, tokens):
    """The held experts' pairs run through XLA's grouped-matmul kernel
    (``ragged_dot``), one call a projection, at a decode step's 4 tokens
    and a 512-token prefill's."""
    from repro.models import moe
    cfg = _dsv2_lite_share()
    p = jax.eval_shape(lambda k: moe.init_moe(k, cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), p)
    x = _spec(one_chip, (tokens, cfg.d_model), jnp.bfloat16)
    text = jax.jit(lambda p, x: moe.moe_dropless(p, x, cfg)).lower(
        p, x).compile().as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%ragged-dot-none\S* = \S+ custom-call\(",
                       text, flags=re.M)
    assert len(calls) == 3, "three grouped-matmul kernels"


def test_dsv2_lite_latent_decode_layer_compiles(one_chip):
    """One latent-attention layer's absorbed decode over the 576-wide
    pool at 4 slots and a 3,072-token table compiles for the chip."""
    from repro.models import mla
    from repro.serving.decode import paged_mla_decode
    cfg = _dsv2_lite_share()
    p = jax.eval_shape(lambda k: mla.init_mla(k, cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: _spec(one_chip, a.shape, jnp.bfloat16), p)
    S = 4
    args = (p, _spec(one_chip, (S, 1, 2048), jnp.bfloat16),
            _spec(one_chip, (27, 1 + S * 192, 16, 576), jnp.bfloat16),
            _spec(one_chip, (), jnp.int32),
            _spec(one_chip, (S, 192), jnp.int32),
            _spec(one_chip, (S,), jnp.int32), _spec(one_chip, (S,), bool))
    compiled = jax.jit(lambda *a: paged_mla_decode(*a, cfg)).lower(
        *args).compile()
    assert compiled.memory_analysis() is not None
