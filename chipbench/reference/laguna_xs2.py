"""Plain reference of the ``laguna_xs2`` configuration: next-token training
of the Laguna decoder (poolside ``laguna``: full and sliding-window layers
of different head counts with a per-head output gate, a leading dense layer,
sigmoid-routed small experts beside a shared expert) forward, loss,
gradients and Adam in straightforward ``jax.numpy``, float32 at ``highest``
matmul precision.

It imports nothing of the program.  No kernels, no mixed precision, no
sorting of positions: attention goes by chunks of queries against ALL keys,
the rule written as a boolean expression of the two indices; the experts are
a loop over the ones held, each applied to all positions and weighted by its
routing weight or 0; the shared expert is one more product on every
position.  Runs of equal layers go through a ``lax.scan`` with the layer
recomputed in the backward pass and the loss through chunks of positions,
so that a step at the timed size fits once the trainer is freed.  For the
same reason ``run`` EMPTIES the ``weights`` dict it is given (the float32
originals go to the host for the final comparison), and Adam's moments wait
on the host while a gradient is computed (gradient and update are two
programs; the update donates its state).

Readings of what the published config leaves open, the same as the
configuration's ``assumed``: the attention gate is per head,
``sigmoid(a W_g)`` from the layer's normed input on that head's output
before the output projection; the router is DeepSeek-V3's (sigmoid scores,
the 8 largest, normalised over the chosen ones, times 2.5) without groups,
bias or auxiliary loss; no QK-norm; the shared expert is added unscaled;
YaRN as ``transformers`` computes it, over the first half of each head of
the full layers.  The share of one chip under expert parallelism: the
router is as wide as published, and only the held experts' parts of the
result are added; a slice of the vocabulary.

``precision="int8"`` or ``"fp8"`` is the control of the output check: the
inputs of every dense layer, of every expert's three products (the shared
expert's too) and of the head are rounded to 8-bit integers, or to float8
(e4m3), with one scale a tensor (straight-through in the backward pass); the
router's product stays in float32, as a PR taking that step would leave it.
``rows=n`` is the planted fault "part of the batch left out, the mean taken
over the rest": the first ``n`` sequences are kept; ``n = 0`` (half of a
batch of ONE sequence) keeps the first half of that sequence's positions.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE = 4096  # elements of a leaf's gradient kept for the difference


def _round(x, quant):
    """``x`` in the control's precision, one scale a tensor; identity
    gradient.  int8: 255 levels over the range; fp8: e4m3, range to 448."""
    if not quant:
        return x
    top = jnp.max(jnp.abs(x)) + 1e-30
    if quant == "int8":
        q = jnp.clip(jnp.round(x * (127.0 / top)), -127, 127) * (top / 127.0)
    else:
        q = (x * (448.0 / top)).astype(jnp.float8_e4m3fn) \
            .astype(jnp.float32) * (top / 448.0)
    return x + jax.lax.stop_gradient(q - x)


def _sample(a, keep_axis0):
    """An evenly spaced SAMPLE of a's elements; ``keep_axis0``: of every
    layer of a stack."""
    a = a.reshape((a.shape[0], -1) if keep_axis0 else (1, -1))
    a = a[:, ::max(1, a.shape[1] // SAMPLE)][:, :SAMPLE]
    return a if keep_axis0 else a[0]


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _dense(v, w, quant):
    """A dense layer (weights (out, in)) on rounded inputs."""
    return _round(v, quant) @ _round(w, quant).T


def rotary_frequencies(rope, head_dim):
    """(inverse frequencies of the rotated dimensions' pairs, factor on cos
    and sin) of one entry of the config's ``rope_parameters``; YaRN as
    ``transformers``' ``_compute_yarn_parameters``."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    base = float(rope["rope_theta"])
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return extra, 1.0
    f, l0 = rope["factor"], rope["original_max_position_embeddings"]

    def c(n):
        return dim * math.log(l0 / (2 * math.pi * n)) / (2 * math.log(base))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extra / f * ramp + extra * (1 - ramp), rope["attention_factor"]


def _rope(x, inv_freq, factor):
    """Rotate-half rotary positions 0..T-1 on the first ``2 len(inv_freq)``
    dimensions of every head of x (b, t, heads, d); the others pass."""
    r = 2 * len(inv_freq)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)                     # (t, r/2)
    cos = factor * jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = factor * jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    head, rest = x[..., :r], x[..., r:]
    rot = jnp.concatenate([-head[..., r // 2:], head[..., :r // 2]], -1)
    return jnp.concatenate([head * cos + rot * sin, rest], -1)


def allowed(i, j, window):
    """May query ``i`` attend to key ``j``?  Not to a later one, and in a
    sliding layer only to the last ``window`` keys."""
    seen = j <= i
    return seen if window is None else seen & (i - j < window)


def _attention(q, k, v, window, chunk):
    """q (b, T, H, d), k/v (b, T, Hkv, d) -> (b, T, H, d); softmax over the
    allowed keys, ``chunk`` queries at a time."""
    b, t, heads, d = q.shape
    kv = k.shape[2]            # query heads g*kv .. g*kv + group share one
    chunk = min(chunk, t)
    if t % chunk:
        raise ValueError("laguna_xs2 reference: %d queries in chunks of %d"
                         % (t, chunk))
    qc = q.reshape(b, t // chunk, chunk, kv, heads // kv, d) \
        .transpose(1, 0, 2, 3, 4, 5)
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one(args):
        qi, start = args
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k) / jnp.sqrt(float(d))
        i = start + jnp.arange(chunk)[:, None]
        s = jnp.where(allowed(i, j, window), s, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, (qc, jnp.arange(t // chunk) * chunk))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, heads, d)


def _gated(x, wg, w1, w2, quant):
    """One gated SiLU expert on rounded ``x``: weights (in, hidden) twice
    and (hidden, out)."""
    a = jax.nn.silu(x @ _round(wg, quant)) * (x @ _round(w1, quant))
    return _round(a, quant) @ _round(w2, quant)


def _moe(cfg, quant, x, lw, chunk=None):
    """x (n, c): route over all the router's experts, add the held ones'
    weighted results and the shared expert's; ``chunk`` positions at a
    time (memory only).  ``lw``: the layer's ``moe.*`` weights; without
    ``moe.shared_*`` there is no shared expert."""
    if chunk and x.shape[0] > chunk:
        part = jax.checkpoint(lambda xc: _moe(cfg, quant, xc, lw))
        return jax.lax.map(part, x.reshape(-1, chunk, x.shape[1])) \
            .reshape(x.shape)
    k, first = cfg["num_experts_per_tok"], cfg["first_expert"]
    gate, w1 = lw["moe.gate"], lw["moe.w1"]
    s = jax.nn.sigmoid(x @ gate.T)
    top_s, top_e = jax.lax.top_k(s, k)
    top_w = cfg["moe_routed_scaling_factor"] * top_s \
        / jnp.sum(top_s, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(top_e, gate.shape[0]) * top_w[..., None],
                     axis=1)[:, first:first + w1.shape[0]]        # (n, held)
    xq = _round(x, quant)

    @jax.checkpoint
    def expert(acc, e):
        e_w1, e_wg, e_w2, w_e = e
        return acc + w_e[:, None] * _gated(xq, e_wg, e_w1, e_w2, quant), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (w1, lw["moe.wg"], lw["moe.w2"], weight.T))
    if "moe.shared_w1" in lw:
        out = out + _gated(xq, lw["moe.shared_wg"], lw["moe.shared_w1"],
                           lw["moe.shared_w2"], quant)
    return out


def layer_groups(cfg):
    """Runs of consecutive layers of one attention type, head count and
    feed-forward kind: ``[(first layer, layers, type, heads, ffn)]``."""
    out = []
    for i, sig in enumerate(zip(cfg["layer_types"],
                                cfg["num_attention_heads_per_layer"],
                                cfg["mlp_layer_types"])):
        if out and out[-1][2:] == sig:
            out[-1] = (out[-1][0], out[-1][1] + 1) + sig
        else:
            out.append((i, 1) + sig)
    return out


def _logits_loss(cfg, quant, rest, h, labels, chunk):
    """Sum over h's (n, c) positions of the cross-entropy against
    ``labels`` (n,), ``chunk`` positions' logits at a time."""
    @jax.checkpoint
    def part(args):
        h_c, labels_c = args
        logits = _dense(_rms(h_c, rest["norm.gamma"], cfg["rms_norm_eps"]),
                        rest["head.weight"], quant)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels_c[:, None],
                                            axis=-1))

    n = h.shape[0]
    chunk = min(chunk, n)
    if n % chunk:
        raise ValueError("laguna_xs2 reference: %d positions in chunks of %d"
                         % (n, chunk))
    return jnp.sum(jax.lax.map(part, (h.reshape(n // chunk, chunk, -1),
                                      labels.reshape(n // chunk, chunk))))


def hidden_states(cfg, traffic, quant, rest, groups, ids):
    """(b, t, c) after the last layer; ``groups``: one dict of stacked
    layer weights a ``layer_groups`` run."""
    b, t = ids.shape
    kv_heads, d = cfg["num_key_value_heads"], cfg["head_dim"]
    eps, ref = cfg["rms_norm_eps"], traffic["reference"]
    h = rest["embed.weight"][ids]
    dense = functools.partial(_dense, quant=quant)

    for (_, _, kind, heads, ffn), stacked in zip(layer_groups(cfg), groups):
        inv_freq, factor = rotary_frequencies(cfg["rope_parameters"][kind], d)
        window = cfg["sliding_window"] if kind == "sliding_attention" \
            else None

        @jax.checkpoint
        def layer(h, lw, heads=heads, ffn=ffn, inv_freq=inv_freq,
                  factor=factor, window=window):
            a = _rms(h, lw["input_norm.gamma"], eps)

            def rows(name, n):
                return dense(a, lw["attention.%s_proj.weight" % name]) \
                    .reshape(b, t, n, d)

            o = _attention(_rope(rows("query", heads), inv_freq, factor),
                           _rope(rows("key", kv_heads), inv_freq, factor),
                           rows("value", kv_heads), window,
                           ref["query_chunk"])
            g = jax.nn.sigmoid(dense(a, lw["attention.gate_proj.weight"]))
            o = (o * g[..., None]).reshape(b, t, heads * d)
            h = h + dense(o, lw["attention.out_proj.weight"])
            m = _rms(h, lw["post_norm.gamma"], eps).reshape(b * t, -1)
            if ffn == "dense":
                m = _gated(_round(m, quant), lw["mlp.gate_proj.weight"].T,
                           lw["mlp.up_proj.weight"].T,
                           lw["mlp.down_proj.weight"].T, quant)
            else:
                m = _moe(cfg, quant, m, lw, ref["position_chunk"])
            return h + m.reshape(h.shape), None

        h, _ = jax.lax.scan(layer, h, stacked)
    return h


def _loss_sum(cfg, traffic, quant, rest, groups, x, y):
    """Sum over the positions of (x, y) of the next-token cross-entropy
    (the caller divides by their number)."""
    h = hidden_states(cfg, traffic, quant, rest, groups, x)
    return _logits_loss(cfg, quant, rest, h.reshape(-1, h.shape[-1]),
                        y.reshape(-1), traffic["reference"]["position_chunk"])


def _adam(opt, t, w, g, m, v):
    b1, b2 = opt["beta1"], opt["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["epsilon"])
    return w - opt["learning_rate"] * step, m, v


def _sq(tree, keep_axis0):
    return {n: jnp.sum(jnp.square(a),
                       axis=tuple(range(int(keep_axis0), a.ndim)))
            for n, a in tree.items()}


def _pack(w, cfg):
    """(everything outside the layers, one dict of stacked layer weights a
    run of equal layers)."""
    rest = {n: a for n, a in w.items() if not n.startswith("layers.")}
    groups = []
    for first, count, *_ in layer_groups(cfg):
        prefix = "layers.%d." % first
        shorts = [n[len(prefix):] for n in w if n.startswith(prefix)]
        groups.append({s: jnp.stack([w["layers.%d.%s" % (i, s)]
                                     for i in range(first, first + count)])
                       for s in shorts})
    return rest, groups


def _named(cfg, rest, groups, leaf=float):
    """Host values under the weights' names; a group holds one a layer."""
    out = {n: leaf(a) for n, a in rest.items()}
    for (first, _, *_), stacked in zip(layer_groups(cfg), groups):
        for short, per_layer in stacked.items():
            for i, a in enumerate(per_layer):
                out["layers.%d.%s" % (first + i, short)] = leaf(a)
    return out


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, traffic_json, quant):
    """The jitted gradient and update, one pair a configuration, traffic
    and precision (a process that follows many seeds traces them once).
    Two programs, not one, so that Adam's moments need not lie on the
    device while the gradient is computed."""
    cfg, traffic = json.loads(cfg_json), json.loads(traffic_json)
    opt = cfg["optimizer"]
    loss_sum = functools.partial(_loss_sum, cfg, traffic, quant)

    @jax.jit
    def grad(w, x, y):
        """Mean loss over the positions of (x, y), and its gradient."""
        total, g = jax.value_and_grad(loss_sum, argnums=(0, 1))(*w, x, y)
        g = jax.tree_util.tree_map(lambda a: a / x.size, g)
        rest, groups = g
        seen = (_sq(rest, 0), [_sq(s, 1) for s in groups],
                {n: _sample(a, False) for n, a in rest.items()},
                [{n: _sample(a, True) for n, a in s.items()}
                 for s in groups])
        return total / x.size, seen, g

    @functools.partial(jax.jit, donate_argnums=(1, 3, 4))
    def update(t, w, g, m, v):
        leaves, treedef = jax.tree_util.tree_flatten(w)
        new = [_adam(opt, t, *a) for a in zip(
            leaves, *(jax.tree_util.tree_leaves(s) for s in (g, m, v)))]
        return tuple(treedef.unflatten([o[i] for o in new])
                     for i in range(3))

    return grad, update


def run(cfg, traffic, weights, batches, precision="float32", rows=None):
    """Follow ``len(batches)`` training steps from ``weights`` (a dict,
    which this EMPTIES: see the module's docstring).

    Returns ``{"losses": [...], "grad_norms": {name: norm of the first
    step's gradient}, "grad_samples": {name: SAMPLE of its elements},
    "delta_norms": {name: norm of the weights' change over all the
    steps}}`` on the host.
    """
    if precision not in ("float32", "int8", "fp8"):
        raise ValueError("laguna_xs2 reference: precision %r" % (precision,))
    quant = None if precision == "float32" else precision
    grad, update = _programs(json.dumps(cfg, sort_keys=True),
                             json.dumps(traffic, sort_keys=True), quant)

    with jax.default_matmul_precision("highest"):
        w = jax.jit(functools.partial(_pack, cfg=cfg))(weights)
        w0 = {n: np.asarray(a) for n, a in weights.items()}   # to the host
        weights.clear()
        moments, losses, seen = None, [], None
        for i, (x, y) in enumerate(batches):
            if rows:
                x, y = x[:rows], y[:rows]
            elif rows == 0:     # half of one sequence: its first positions
                x, y = x[:, :x.shape[1] // 2], y[:, :y.shape[1] // 2]
            loss, new, g = grad(w, x, y)
            losses.append(float(loss))
            seen = jax.device_get(new) if i == 0 else seen
            # Adam's moments wait on the HOST while a gradient is computed
            m, v = jax.tree_util.tree_map(jnp.zeros_like, (w, w)) \
                if moments is None else jax.device_put(moments)
            w, m, v = update(jnp.float32(i + 1), w, g, m, v)
            if i + 1 < len(batches):
                moments = jax.device_get((m, v))
            del g, m, v
        final = _named(cfg, *jax.device_get(w), leaf=lambda a: a)
    g_rest, g_groups, s_rest, s_groups = seen
    delta = {n: float(np.linalg.norm(
        (final[n].astype(np.float64) - w0[n]).ravel())) for n in w0}

    def norm(a):
        return float(a) ** 0.5

    return {"losses": losses,
            "grad_norms": _named(cfg, g_rest, g_groups, norm),
            "grad_samples": _named(cfg, s_rest, s_groups, lambda a: a),
            "delta_norms": delta}
