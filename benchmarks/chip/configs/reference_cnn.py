"""Plain reference of a CNN configuration and of one round of the
grouped training step (Omnivore's compute groups with a merged FC head).

It imports nothing of the program. Everything is ``jax.numpy`` and
``lax`` at ``Precision.HIGHEST``: float32 by default, or the control's
lower precision through ``dtype``.

One round on a global batch of B rows split into g contiguous groups:
every group's gradient is taken at the round-start parameters; the conv
(backbone) leaves take g momentum sub-steps in group order,
``v = mu * v - lr * grad_i; p = p + v``; the fc (head) leaves take one
sub-step with the mean of the g gradients. The reported loss is the mean
over groups of each group's mean cross-entropy.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def forward(params, images, cfg: Dict, dtype=jnp.float32):
    x = images.astype(dtype)
    for (feat, k, stride, pool), p in zip(cfg["convs"], params["conv"]):
        x = lax.conv_general_dilated(
            x, p["w"].astype(dtype), (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
            preferred_element_type=dtype)
        x = jnp.maximum(x + p["b"].astype(dtype), 0)
        if pool > 1:
            x = lax.reduce_window(x, -jnp.inf, lax.max,
                                  (1, pool, pool, 1), (1, pool, pool, 1),
                                  "VALID")
    x = x.reshape(x.shape[0], -1)
    for i, p in enumerate(params["fc"]):
        x = jnp.dot(x, p["w"].astype(dtype), precision=HI,
                    preferred_element_type=dtype) + p["b"].astype(dtype)
        if i < len(params["fc"]) - 1:
            x = jnp.maximum(x, 0)
    return x


def loss(params, batch, cfg: Dict, dtype=jnp.float32):
    logits = forward(params, batch["images"], cfg, dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)
    return -picked.mean()


def train_round(params, mom, batch, cfg: Dict, *, groups: int, lr: float,
                momentum: float, dtype=jnp.float32,
                keep_rows: Optional[float] = None):
    """One round; returns (params, mom, loss). ``keep_rows`` plants a
    fault for the tests and the fault readings: each group's gradient and
    loss are taken over only that share of its rows."""
    B = batch["labels"].shape[0]
    per = B // groups
    grads, losses = [], []
    for i in range(groups):
        rows = per if keep_rows is None else max(1, int(per * keep_rows))
        sl = {k: v[i * per:i * per + rows] for k, v in batch.items()}
        l, gr = jax.value_and_grad(loss)(params, sl, cfg, dtype)
        grads.append(gr)
        losses.append(l)
    lr_ = jnp.asarray(lr, dtype)
    mu = jnp.asarray(momentum, dtype)
    new_p: Dict = {"conv": [], "fc": []}
    new_v: Dict = {"conv": [], "fc": []}
    for part in ("conv", "fc"):
        for j, (pl, vl) in enumerate(zip(params[part], mom[part])):
            pd, vd = {}, {}
            for name in pl:
                p, v = pl[name].astype(dtype), vl[name].astype(dtype)
                if part == "conv":
                    for gr in grads:
                        v = mu * v - lr_ * gr[part][j][name]
                        p = p + v
                else:
                    gbar = sum(gr[part][j][name] for gr in grads) / groups
                    v = mu * v - lr_ * gbar
                    p = p + v
                pd[name], vd[name] = p, v
            new_p[part].append(pd)
            new_v[part].append(vd)
    return new_p, new_v, sum(losses) / groups


def run_rounds(params, batches, cfg: Dict, *, groups: int, lr: float,
               momentum: float, dtype=jnp.float32, keep_rows=None):
    """Rounds from zero momentum over ``batches``. Returns the losses, the
    momentum after the first round and the parameters after the last."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    mom = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(lambda p, v, b: train_round(
        p, v, b, cfg, groups=groups, lr=lr, momentum=momentum, dtype=dtype,
        keep_rows=keep_rows))
    losses, mom1 = [], None
    for b in batches:
        params, mom, l = step(params, mom, b)
        losses.append(float(l))
        if mom1 is None:
            mom1 = mom
    return losses, mom1, params
