"""``chip_smoke.py`` rehearsed on the CPU: each phase function at smoke
widths (the same code the chip run drives at full width), the refusal to
run without a TPU, the compile-cache placement the entry points use, and
the lowering-conv kernels' refusal to run on a TPU backend."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_train_phase_smoke():
    out = chip_smoke.train_phase(smoke=True, batch=16, rounds=3, groups=4)
    assert out["mismatch"] <= chip_smoke.UPDATE_TOL
    for impl in ("xla", "pallas"):
        assert len(out[impl]["losses"]) == 3
        assert np.isfinite(out[impl]["losses"]).all()
        assert out[impl]["median_step_ms"] is not None
    # the same batches and init: the first round's loss is the same
    assert out["xla"]["losses"][0] == pytest.approx(out["pallas"]["losses"][0])


def test_serve_phase_smoke():
    out = chip_smoke.serve_phase(smoke=True, slots=4, page=8, max_seq=32,
                                 requests=4, prompt_range=(4, 12), gen=4)
    assert out["logit_diff"] <= out["logit_tol"]
    assert out["kernel_err"] <= chip_smoke.KERNEL_TOL
    for impl in ("xla", "pallas"):
        assert out[impl]["warmup_s"] > 0


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs 4 host devices (tests/conftest.py forces 8)")
def test_spmd_phase_smoke():
    out = chip_smoke.spmd_phase(smoke=True, batch=16, rounds=2)
    assert set(out) == {(2, 2, 1), (2, 1, 2)}
    for r in out.values():
        assert r["mismatch"] <= chip_smoke.UPDATE_TOL


@pytest.mark.parametrize("chips", ["1", "4"])
def test_main_refuses_without_tpu(chips, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main(["--chips", chips]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs" in out.err


def test_compile_cache_placement(monkeypatch):
    from repro.launch import compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None   # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_lowering_conv_pallas_raises_on_tpu(monkeypatch):
    import jax.numpy as jnp

    from repro import kernels
    from repro.kernels.lowering_conv import bwd
    from repro.kernels.lowering_conv.lowering_conv import lowering_conv_pallas
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    x = jnp.ones((2, 8, 8, 3))
    w = jnp.ones((3, 3, 3, 4))
    with pytest.raises(NotImplementedError, match="lowering_conv"):
        lowering_conv_pallas(x, w)
    with pytest.raises(NotImplementedError, match="lowering_conv"):
        bwd.dgrad_pallas(jnp.ones((2, 6, 6, 4)), w, x.shape)
