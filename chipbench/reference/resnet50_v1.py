"""Plain reference of the ``resnet50_v1`` configuration: ResNet-50 (He et
al., arXiv:1512.03385; stride in a stage's first 1x1 convolution, biased
1x1 convolutions, as Gluon's ``resnet50_v1``) forward, softmax cross-entropy,
gradients, BatchNorm's running statistics and SGD with momentum, in
straightforward ``jax.numpy``/``lax`` float32 at ``highest`` precision.

It imports nothing of the program.  BatchNorm couples the rows of a batch,
so the batch cannot go through in blocks of rows: instead every bottleneck
block is recomputed in the backward pass (``jax.checkpoint``), layer by
layer, so that a step at the timed size fits on the chip.

``precision="int8"`` or ``"fp8"`` is the control of the output check (the
inputs of every convolution and of the classifier rounded, one scale a
tensor, straight-through in the backward pass); ``rows=n`` the planted fault
"part of the batch left out".
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

SAMPLE = 4096  # elements of a leaf's gradient kept for the difference


def _round(x, quant):
    if not quant:
        return x
    top = jnp.max(jnp.abs(x)) + 1e-30
    if quant == "int8":
        q = jnp.clip(jnp.round(x * (127.0 / top)), -127, 127) * (top / 127.0)
    else:
        q = (x * (448.0 / top)).astype(jnp.float8_e4m3fn) \
            .astype(jnp.float32) * (top / 448.0)
    return x + lax.stop_gradient(q - x)


def _conv(w, name, x, stride, pad, quant):
    y = lax.conv_general_dilated(
        _round(x, quant), _round(w[name + ".weight"], quant),
        (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if name + ".bias" in w:
        y = y + w[name + ".bias"][None, :, None, None]
    return y


def _bn(cfg, w, stats, name, x):
    """Training-mode BatchNorm over (N, H, W); records the new running
    statistics (biased variance, as the program's) in ``stats``."""
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(x - mean[None, :, None, None]), axis=(0, 2, 3))
    mom = cfg["bn_momentum"]
    stats[name + ".running_mean"] = \
        w[name + ".running_mean"] * mom + lax.stop_gradient(mean) * (1 - mom)
    stats[name + ".running_var"] = \
        w[name + ".running_var"] * mom + lax.stop_gradient(var) * (1 - mom)
    inv = w[name + ".gamma"] / jnp.sqrt(var + cfg["bn_epsilon"])
    return (x - mean[None, :, None, None]) * inv[None, :, None, None] \
        + w[name + ".beta"][None, :, None, None]


def _block(cfg, quant, p, stride, downsample, w, x):
    stats = {}
    conv = functools.partial(_conv, w, quant=quant)
    h = jax.nn.relu(_bn(cfg, w, stats, p + "body.1",
                        conv(p + "body.0", x, stride, 0)))
    h = jax.nn.relu(_bn(cfg, w, stats, p + "body.4",
                        conv(p + "body.3", h, 1, 1)))
    h = _bn(cfg, w, stats, p + "body.7", conv(p + "body.6", h, 1, 0))
    if downsample:
        x = _bn(cfg, w, stats, p + "downsample.1",
                conv(p + "downsample.0", x, stride, 0))
    return jax.nn.relu(h + x), stats


def _loss(cfg, quant, w, x, y):
    """(mean loss over the rows, new running statistics)."""
    stem, stats = cfg["stem"], {}
    h = _conv(w, "features.0", x, stem["stride"], stem["pad"], quant)
    h = jax.nn.relu(_bn(cfg, w, stats, "features.1", h))
    k, s, p = stem["pool_kernel"], stem["pool_stride"], stem["pool_pad"]
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, s, s),
                          [(0, 0), (0, 0), (p, p), (p, p)])
    for stage, n_blocks in enumerate(cfg["layers"]):
        for block in range(n_blocks):
            prefix = "features.%d.%d." % (stage + 4, block)
            mine = {n: a for n, a in w.items() if n.startswith(prefix)}
            h, new = jax.checkpoint(functools.partial(
                _block, cfg, quant, prefix,
                2 if (block == 0 and stage > 0) else 1, block == 0))(mine, h)
            stats.update(new)
    h = jnp.mean(h, axis=(2, 3))
    logits = _round(h, quant) @ _round(w["output.weight"], quant).T \
        + w["output.bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1)), stats


def _sample(a):
    a = a.ravel()
    return a[::max(1, a.size // SAMPLE)][:SAMPLE]


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, quant):
    """The jitted step and the change's norms, one pair a configuration and
    precision (a process that follows many seeds traces them once)."""
    cfg = json.loads(cfg_json)
    lr, mom = (cfg["optimizer"][k] for k in ("learning_rate", "momentum"))

    @jax.jit
    def step(w, m, x, y):
        train = {n: a for n, a in w.items() if ".running_" not in n}
        rest = {n: a for n, a in w.items() if ".running_" in n}
        (loss, stats), g = jax.value_and_grad(
            lambda t: _loss(cfg, quant, {**t, **rest}, x, y),
            has_aux=True)(train)
        m = {n: mom * m[n] - lr * g[n] for n in g}
        new = {n: train[n] + m[n] for n in g}
        new.update(stats)
        seen = ({n: jnp.sqrt(jnp.sum(jnp.square(a))) for n, a in g.items()},
                {n: _sample(a) for n, a in g.items()})
        return loss, seen, new, m

    @jax.jit
    def change(w, w0):
        return {n: jnp.sqrt(jnp.sum(jnp.square(w[n] - w0[n]))) for n in w}

    return step, change


def run(cfg, traffic, weights, batches, precision="float32", rows=None):
    """Follow ``len(batches)`` training steps from ``weights``.

    Returns ``{"losses", "grad_norms", "grad_samples", "delta_norms"}`` on
    the host; ``delta_norms`` covers the running statistics too."""
    if precision not in ("float32", "int8", "fp8"):
        raise ValueError("resnet50_v1 reference: precision %r" % (precision,))
    quant = None if precision == "float32" else precision
    frozen = {n for n in weights if ".running_" in n}
    step, change = _programs(json.dumps(cfg, sort_keys=True), quant)

    with jax.default_matmul_precision("highest"):
        w = dict(weights)
        m = {n: jnp.zeros_like(a) for n, a in w.items() if n not in frozen}
        losses, seen = [], None
        for i, (x, y) in enumerate(batches):
            if rows is not None:
                x, y = x[:rows], y[:rows]
            loss, new, w, m = step(w, m, x, y)
            losses.append(float(loss))
            seen = new if i == 0 else seen
        delta = change(w, dict(weights))
    (norms, samples), delta = jax.device_get((seen, delta))
    return {"losses": losses,
            "grad_norms": {n: float(a) for n, a in norms.items()},
            "grad_samples": samples,
            "delta_norms": {n: float(a) for n, a in delta.items()}}
