"""Builder of the ``resnet50_v1`` configuration: weights and batches from a
key, the program's trainer, and the count of model operations.

Only ``make_trainer`` touches the program.  Weights carry the program's
parameter names (Gluon's ``resnet50_v1``: ``features.<i>...``, ``output``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

UNIT = "images"
_BN = (("gamma", "ones"), ("beta", "zeros"), ("running_mean", "zeros"),
       ("running_var", "ones"))


def convolutions(cfg):
    """Every convolution as (name, c_out, c_in, kernel, stride, pad,
    bias, output height) in forward order, and the classifier's (out, in)."""
    ch, size = cfg["channels"], cfg["image_size"]
    stem = cfg["stem"]

    def out(h, k, s, p):
        return (h + 2 * p - k) // s + 1

    h = out(size, stem["kernel"], stem["stride"], stem["pad"])
    convs = [("features.0", ch[0], 3, stem["kernel"], stem["stride"],
              stem["pad"], False, h)]
    h = out(h, stem["pool_kernel"], stem["pool_stride"], stem["pool_pad"])
    c_in = ch[0]
    bias = cfg["conv1x1_bias"]
    for stage, n_blocks in enumerate(cfg["layers"]):
        c_out = ch[stage + 1]
        mid = c_out // cfg["bottleneck_width"]
        for block in range(n_blocks):
            stride = 2 if (block == 0 and stage > 0) else 1
            p = "features.%d.%d." % (stage + 4, block)
            h_out = out(h, 1, stride, 0)
            convs.append((p + "body.0", mid, c_in, 1, stride, 0, bias, h_out))
            convs.append((p + "body.3", mid, mid, 3, 1, 1, False, h_out))
            convs.append((p + "body.6", c_out, mid, 1, 1, 0, bias, h_out))
            if block == 0:
                convs.append((p + "downsample.0", c_out, c_in, 1, stride, 0,
                              False, h_out))
            c_in, h = c_out, h_out
    return convs, (cfg["classes"], c_in)


def weight_shapes(cfg):
    """{program parameter name: (shape, kind, fan_in)}."""
    convs, (classes, c_last) = convolutions(cfg)
    bn_of = {"features.0": "features.1", "body.0": "body.1",
             "body.3": "body.4", "body.6": "body.7",
             "downsample.0": "downsample.1"}
    out = {}
    for name, c_out, c_in, k, _s, _p, bias, _h in convs:
        out[name + ".weight"] = ((c_out, c_in, k, k), "he", c_in * k * k)
        if bias:
            out[name + ".bias"] = ((c_out,), "zeros", None)
        for tail, bn_tail in bn_of.items():
            if name.endswith(tail):
                bn = name[:-len(tail)] + bn_tail
        for leaf, kind in _BN:
            if leaf == "gamma" and name.endswith("body.6"):
                kind = "last_gamma"  # a block's last BatchNorm
            out["%s.%s" % (bn, leaf)] = ((c_out,), kind, None)
    out["output.weight"] = ((classes, c_last), "classifier", None)
    out["output.bias"] = ((classes,), "zeros", None)
    return out


def make_weights(cfg, key):
    out = {}
    for i, (name, (shape, kind, fan_in)) in enumerate(
            weight_shapes(cfg).items()):
        if kind in ("he", "classifier"):
            std = (2.0 / fan_in) ** 0.5 if kind == "he" else 0.01
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        else:
            value = {"ones": 1.0, "zeros": 0.0,
                     "last_gamma": cfg["last_bn_gamma"]}[kind]
            out[name] = jnp.full(shape, value, jnp.float32)
    return out


def make_batch(cfg, traffic, key):
    """Images as a loader hands them over after mean/std normalisation:
    zero mean, unit scale, and different from row to row at every scale, as
    photographs are: a random coarse field (one value a 32x32 patch and
    channel) under pixel noise.  Raw pixel noise in [0, 1) makes every image
    the same to a convolution but for a small residue, and the first
    gradient a difference of near-equal terms that no 8-bit mantissa can
    carry (PERF.md, finding on the ResNet check)."""
    b, s = traffic["batch"], cfg["image_size"]
    k = jax.random.split(key, 3)
    low = max(1, s // 32)
    coarse = jax.random.normal(k[0], (b, 3, low, low), jnp.float32)
    coarse = jnp.repeat(jnp.repeat(coarse, s // low, axis=2), s // low, axis=3)
    x = coarse + 0.5 * jax.random.normal(k[1], (b, 3, s, s), jnp.float32)
    y = jax.random.randint(k[2], (b,), 0, cfg["classes"], jnp.int32)
    return x, y


def forward_macs(cfg):
    """Multiply-adds of one image's forward pass: convolutions and the
    classifier, from the shapes."""
    convs, (classes, c_last) = convolutions(cfg)
    return sum(c_out * c_in * k * k * h * h
               for _n, c_out, c_in, k, _s, _p, _b, h in convs) \
        + classes * c_last


def ops_per_step(cfg, traffic):
    """Model operations of one training step: multiply-adds x 2, forward
    x 3 (forward, input gradients, weight gradients), nothing recomputed.
    (bench.py's 3 x 4.089 GFLOP an image counts multiply-adds as operations
    and the v1.5 stride placement; this count is 3 x 7.7 GFLOP.)"""
    return 3.0 * 2.0 * forward_macs(cfg) * traffic["batch"]


def units_per_step(cfg, traffic):
    return traffic["batch"]


def make_trainer(cfg, weights, mesh):
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo.vision import resnet as zoo

    if cfg["bottleneck_width"] != 4 or not cfg["conv1x1_bias"]:
        raise SystemExit("resnet50_v1: the program's BottleneckV1 has width "
                         "channels/4 and biased 1x1 convolutions")
    net = zoo.ResNetV1(zoo.BottleneckV1, cfg["layers"], cfg["channels"],
                       classes=cfg["classes"])
    params = net.collect_params()
    if set(params) != set(weights):
        raise SystemExit("resnet50_v1: the program's parameters are not the "
                         "ones this builder makes: %s"
                         % sorted(set(params) ^ set(weights))[:6])
    for name, p in params.items():
        p.set_data(weights[name])
    opt = dict(cfg["optimizer"])
    return parallel.FusedTrainer(
        net, loss="softmax_ce", optimizer=opt.pop("name"),
        optimizer_params=opt, dtype=cfg["compute_dtype"], mesh=mesh)


def program_names(weights):
    return {n: n for n in weights}


def first_gradient(cfg, state_leaf):
    """SGD with momentum: the state after step 1 is -lr * g."""
    return -state_leaf / cfg["optimizer"]["learning_rate"]
