"""Pallas kernels for the hot spots the paper optimizes (lowering conv,
fused grouped update) and for the serving decode path (paged and flash
attention), plus the one rule that decides how every kernel runs."""
import jax


def interpret_mode() -> bool:
    """True exactly when the default backend is not a TPU: Pallas kernels
    compile natively on a TPU and run in the Pallas interpreter everywhere
    else. Every kernel wrapper asks this at trace time; none takes an
    ``interpret`` argument, so no path can run a kernel interpreted on
    the chip."""
    return jax.default_backend() != "tpu"
