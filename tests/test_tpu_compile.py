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

import jax
import jax.numpy as jnp
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


def test_phi4_paged_decode_step_compiles(one_chip):
    """The serving decode step of phi4-mini at full width, 2 layers, bf16
    weights, 8 slots of 512 tokens, through the paged kernel."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import PagedCacheSpec, init_pages, paged_decode_step
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b"), num_layers=2,
                              param_dtype="bfloat16")
    slots = 8
    spec = PagedCacheSpec.for_config(cfg, num_slots=slots, page_size=16,
                                     max_seq=512)
    place = lambda t: jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), t)
    params = place(jax.eval_shape(lambda k: T.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
    pages = place(jax.eval_shape(lambda: init_pages(spec)))
    f = jax.jit(lambda p, pg, tbl, tok, pos, act: paged_decode_step(
        p, pg, tbl, tok, pos, act, cfg, attn_impl="pallas"))
    compiled = f.lower(params, pages,
                       _spec(one_chip, (slots, spec.pages_per_slot),
                             jnp.int32),
                       _spec(one_chip, (slots, 1), jnp.int32),
                       _spec(one_chip, (slots,), jnp.int32),
                       _spec(one_chip, (slots,), jnp.bool_)).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    # 2 layers of bf16 weights + pools fit one 16 GB chip with room
    assert mem.argument_size_in_bytes < 4 << 30
