"""The paper's own CNN workloads (CaffeNet/AlexNet-family, LeNet) with the
conv-phase / FC-phase split made explicit (paper §II-C, Fig. 1) — the split
drives the hardware-efficiency model and the merged-FC ("sync head") update.

Conv layers run through ``repro.kernels.lowering_conv.ops`` — the paper's
§III batched lowering with the custom batched-GEMM backward — and the
configs default to it (``conv_impl="lowering"``): this is the training hot
path, not a demo. ``conv_impl``:

  "lowering"            lowering + GEMM with the custom VJP (XLA form, the
                        CPU training path; docs/lowering_conv.md)
  "lowering_interpret"  the Pallas kernels (Pallas interpreter only: on a
                        TPU they raise NotImplementedError), tiles from
                        the per-layer autotune cache
  "lowering_autodiff"   the same algorithm under generic XLA autodiff
                        (benchmark baseline)
  "xla"                 jax.lax.conv_general_dilated

The first conv layer is fed by data, so its input gradient is skipped
(``needs_dgrad=False`` — Caffe's ``propagate_down=false``; generic
autodiff gets the same from DCE).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    features: int
    kernel: int
    stride: int = 1
    pool: int = 1          # max-pool window/stride after the conv (1 = none)


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    image_size: int
    in_channels: int
    num_classes: int
    convs: Tuple[ConvSpec, ...]
    fc_dims: Tuple[int, ...]
    # xla | lowering | lowering_interpret | lowering_autodiff (see module
    # docstring); "lowering" is the real training path
    conv_impl: str = "lowering"
    source: str = ""


LENET = CNNConfig(
    name="lenet", image_size=28, in_channels=1, num_classes=10,
    convs=(ConvSpec(20, 5, pool=2), ConvSpec(50, 5, pool=2)),
    fc_dims=(500,),
    source="LeCun 1998 / Caffe MNIST tutorial (paper Fig. 8)")

# CaffeNet geometry (paper's main workload), scaled-down option for CPU runs.
CAFFENET = CNNConfig(
    name="caffenet", image_size=227, in_channels=3, num_classes=1000,
    convs=(ConvSpec(96, 11, stride=4, pool=2), ConvSpec(256, 5, pool=2),
           ConvSpec(384, 3), ConvSpec(384, 3), ConvSpec(256, 3, pool=2)),
    fc_dims=(4096, 4096),
    source="Krizhevsky 2012 / BVLC reference CaffeNet (paper §VI-A)")

CIFAR_NET = CNNConfig(
    name="cifarnet", image_size=32, in_channels=3, num_classes=10,
    convs=(ConvSpec(32, 5, pool=2), ConvSpec(32, 5, pool=2), ConvSpec(64, 5, pool=2)),
    fc_dims=(64,),
    source="Caffe CIFAR-10 tutorial (paper Fig. 8)")

CNN_CONFIGS = {c.name: c for c in (LENET, CAFFENET, CIFAR_NET)}


def get_cnn_config(name: str) -> CNNConfig:
    try:
        return CNN_CONFIGS[name]
    except KeyError:
        raise KeyError(f"unknown CNN arch {name!r}; "
                       f"known: {sorted(CNN_CONFIGS)}") from None


# Per-arch smoke geometry: shrink image/channels/classes but KEEP each
# family's defining structure — caffenet's strided big-kernel conv1,
# cifarnet's three pooled convs — so the smoke runs exercise stride > 1
# and pooling the way the full archs do (the conv-backward test matrix
# and the throughput bench both key off this).
_SMOKE_GEOMETRY = {
    "lenet": dict(image_size=16, convs=(ConvSpec(8, 5, pool=2),
                                        ConvSpec(16, 3)), fc_dims=(32,)),
    "caffenet": dict(image_size=33, convs=(ConvSpec(16, 7, stride=2, pool=2),
                                           ConvSpec(32, 3)), fc_dims=(64,)),
    "cifarnet": dict(image_size=20, convs=(ConvSpec(8, 5, pool=2),
                                           ConvSpec(16, 3, pool=2)),
                     fc_dims=(16,)),
}


def get_cnn_smoke_config(name: str) -> CNNConfig:
    """CPU-runnable reduced same-family config (the CNN counterpart of
    ``configs.get_smoke_config``): shrink the image but keep the conv/FC
    phase split AND the family's conv structure (strides/pools), so the
    merged-FC head semantics and the conv-backward paths stay exercised."""
    base = get_cnn_config(name)
    return dataclasses.replace(
        base, name=f"{base.name}-smoke", num_classes=4,
        **_SMOKE_GEOMETRY[base.name])


def _conv(x, w, b, stride, impl, needs_dgrad=True):
    if impl.startswith("lowering"):
        # _traced forms: the loss is always inside the engine's jit (and
        # possibly its group-vmap) — a nested jit there costs ~2x on CPU
        from repro.kernels.lowering_conv import autotune, ops as lc_ops
        if impl.endswith("interpret"):    # Pallas kernels (interpreter)
            bp, rb = autotune.cached_tiles(x.shape, w.shape, stride)
            y = lc_ops.lowering_conv_traced(x, w, stride=stride, bp=bp,
                                            rb=rb, needs_dgrad=needs_dgrad)
        elif impl.endswith("autodiff"):   # generic-autodiff baseline
            from repro.kernels.lowering_conv.ref import lowered_conv_ref
            y = lowered_conv_ref(x, w, stride=stride)
        else:                             # custom VJP through XLA
            y = lc_ops.lowering_conv_xla_traced(x, w, stride=stride,
                                                needs_dgrad=needs_dgrad)
    else:
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=(stride, stride), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _maxpool(x, k):
    """Non-overlapping max pool. reshape+max instead of reduce_window:
    XLA CPU lowers reduce_window (and its select-and-scatter backward) to
    slow scalar loops that dominated the whole CNN step; the reshape form
    is a dense vectorized max with a cheap backward. VALID semantics:
    trailing rows/cols that don't fill a window are dropped."""
    if k == 1:
        return x
    b, h, w, c = x.shape
    x = x[:, :h // k * k, :w // k * k, :]
    return x.reshape(b, h // k, k, w // k, k, c).max(axis=(2, 4))


def init_params(key, cfg: CNNConfig):
    """Returns {"conv": [...], "fc": [...]} — the paper's two phases."""
    keys = jax.random.split(key, len(cfg.convs) + len(cfg.fc_dims) + 1)
    conv_params = []
    c_in = cfg.in_channels
    size = cfg.image_size
    for i, spec in enumerate(cfg.convs):
        w = jax.random.normal(keys[i], (spec.kernel, spec.kernel, c_in,
                                        spec.features)) * 0.01
        conv_params.append({"w": w, "b": jnp.zeros((spec.features,))})
        size = (size - spec.kernel) // spec.stride + 1
        size = size // spec.pool if spec.pool > 1 else size
        c_in = spec.features
    flat = size * size * c_in
    fc_params = []
    dims = (flat,) + tuple(cfg.fc_dims) + (cfg.num_classes,)
    for j in range(len(dims) - 1):
        k = keys[len(cfg.convs) + j]
        w = jax.random.normal(k, (dims[j], dims[j + 1])) * (dims[j] ** -0.5)
        fc_params.append({"w": w, "b": jnp.zeros((dims[j + 1],))})
    return {"conv": conv_params, "fc": fc_params}


def forward(params, images, cfg: CNNConfig):
    """images: (B,H,W,C) -> logits (B,num_classes)."""
    x = images
    for i, (spec, p) in enumerate(zip(cfg.convs, params["conv"])):
        # layer 0 is fed by data: no input gradient (see module docstring)
        x = jax.nn.relu(_conv(x, p["w"], p["b"], spec.stride, cfg.conv_impl,
                              needs_dgrad=i > 0))
        x = _maxpool(x, spec.pool)
    x = x.reshape(x.shape[0], -1)
    for i, p in enumerate(params["fc"]):
        x = x @ p["w"] + p["b"]
        if i < len(params["fc"]) - 1:
            x = jax.nn.relu(x)
    return x


def loss_fn(params, batch, cfg: CNNConfig):
    logits = forward(params, batch["images"], cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1).mean()


def conv_layer_shapes(cfg: CNNConfig, batch_size: int):
    """[(x_shape, w_shape, stride), ...] for each conv layer — the shapes
    the tile autotuner and the conv-backward tests iterate."""
    out = []
    c_in, size = cfg.in_channels, cfg.image_size
    for spec in cfg.convs:
        out.append(((batch_size, size, size, c_in),
                    (spec.kernel, spec.kernel, c_in, spec.features),
                    spec.stride))
        size = (size - spec.kernel) // spec.stride + 1
        size = size // spec.pool if spec.pool > 1 else size
        c_in = spec.features
    return out


def autotune_conv_tiles(cfg: CNNConfig, batch_size: int, **kw):
    """Probe and cache (b_p, r_b) for every conv layer of ``cfg`` (only
    meaningful for conv_impl="lowering_interpret", which reads the cache).
    Returns {layer_index: (bp, rb)}."""
    from repro.kernels.lowering_conv import autotune
    choices = {}
    for i, (x_shape, w_shape, stride) in enumerate(
            conv_layer_shapes(cfg, batch_size)):
        choices[i] = autotune.autotune_tiles(x_shape, w_shape, stride, **kw)
    return choices


def head_filter(path) -> bool:
    """True for FC-phase params — the paper's merged-FC servers update these
    synchronously (zero staleness)."""
    return any(getattr(p, "key", getattr(p, "name", None)) == "fc"
               or (isinstance(p, jax.tree_util.DictKey) and p.key == "fc")
               for p in path)
