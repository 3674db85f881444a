"""Plain reference of the ``sdar_30b_a3b`` configuration: block-diffusion
training (BD3-LMs, Arriola et al. arXiv:2503.09573, as SDAR uses it) of the
SDAR-MoE decoder (JetLM ``sdar_moe``: Qwen3-MoE's layer) forward, loss,
gradients and Adam in straightforward ``jax.numpy``, float32 at ``highest``
matmul precision.

It imports nothing of the program.  No kernels, no mixed precision, no
sorting of positions: attention goes by chunks of queries against ALL keys,
the rule written as a boolean expression of the two indices; the experts are
a loop over the ones held, each applied to all positions and weighted by its
routing weight or 0.  The batch goes through in blocks of rows and the
layers through a ``lax.scan`` with the layer recomputed in the backward
pass, so that a step at the timed size fits once the trainer is freed.  For
the same reason ``run`` EMPTIES the ``weights`` dict it is given (the float32
originals go to the host for the final comparison), and Adam's moments wait
on the host while a gradient is computed (gradient and update are two
programs; the update donates its state).

Departures from the published model, the same as the configuration's
``assumed``: per-head RMSNorm of q and k with gains (QK-norm; the config
has no key for it, the family's modelling code has it); block length 4;
``t`` a block ~ U(t_min, 1], a token masked with probability ``t``, the
masked tokens' cross-entropy weighted by ``1/t`` (the linear schedule);
the share of one chip under expert parallelism: the router is as wide as
published, and only the held experts' parts of the result are added; a
slice of the vocabulary.  Masking, ``t`` and the weights are made by the
harness and arrive in the batch.

``precision="int8"`` or ``"fp8"`` is the control of the output check: the
inputs of every dense layer, of every expert's three products and of the
head are rounded to 8-bit integers, or to float8 (e4m3), with one scale a
tensor (straight-through in the backward pass); the router's product stays
in float32, as a PR taking that step would leave it.  ``rows=n`` is the
planted fault "part of the batch left out, the mean taken over the rest".
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

_DENSE = ("attention.query_proj.weight", "attention.key_proj.weight",
          "attention.value_proj.weight", "attention.out_proj.weight")
_LAYER = _DENSE + ("attention.query_norm.gamma", "attention.key_norm.gamma",
                   "input_norm.gamma", "post_norm.gamma", "moe.gate",
                   "moe.w1", "moe.wg", "moe.w2")

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
    a = a.reshape((a.shape[0], -1) if keep_axis0 else (1, -1))
    return a[:, ::max(1, a.shape[1] // SAMPLE)][:, :SAMPLE]


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, positions, theta):
    """Rotate-half rotary positions on x (b, t, heads, d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv          # (t, d/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def allowed(i, j, seq, block):
    """May position ``i`` of ``[xt ; x0]`` attend to position ``j``?  Noisy
    ``i``: noisy ``j`` of its own block, clean ``j`` of earlier blocks;
    clean ``i``: clean ``j`` of its own and earlier blocks."""
    i_noisy, j_noisy = i < seq, j < seq
    bi, bj = (i % seq) // block, (j % seq) // block
    return (i_noisy & j_noisy & (bi == bj)) | (i_noisy & ~j_noisy & (bj < bi)) \
        | (~i_noisy & ~j_noisy & (bj <= bi))


def _attention(q, k, v, seq, block, chunk):
    """q (b, T, H, d), k/v (b, T, Hkv, d) -> (b, T, H*d); softmax over the
    allowed keys, ``chunk`` queries at a time."""
    b, t, heads, d = q.shape
    kv = k.shape[2]            # query heads g*kv .. g*kv + group share one
    chunk = min(chunk, t)
    qc = q.reshape(b, t // chunk, chunk, kv, heads // kv, d) \
        .transpose(1, 0, 2, 3, 4, 5)
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one(args):
        qi, start = args
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k) / jnp.sqrt(float(d))
        i = start + jnp.arange(chunk)[:, None]
        s = jnp.where(allowed(i, j, seq, block), s, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, (qc, jnp.arange(t // chunk) * chunk))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, heads * d)


def _moe(cfg, quant, x, gate, w1, wg, w2, chunk=None):
    """x (n, c): route over all the router's experts, add the held ones'
    weighted results; ``chunk`` positions at a time (memory only)."""
    if chunk and x.shape[0] > chunk:
        part = jax.checkpoint(
            lambda xc: _moe(cfg, quant, xc, gate, w1, wg, w2))
        return jax.lax.map(part, x.reshape(-1, chunk, x.shape[1])) \
            .reshape(x.shape)
    k, first = cfg["num_experts_per_tok"], cfg["first_expert"]
    p = jax.nn.softmax(x @ gate.T, axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(top_e, gate.shape[0]) * top_p[..., None],
                     axis=1)[:, first:first + w1.shape[0]]        # (n, held)
    xq = _round(x, quant)

    @jax.checkpoint
    def expert(acc, e):
        e_w1, e_wg, e_w2, w_e = e
        a = jax.nn.silu(xq @ _round(e_wg, quant)) * (xq @ _round(e_w1, quant))
        return acc + w_e[:, None] * (_round(a, quant) @ _round(e_w2, quant)), \
            None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), (w1, wg, w2, weight.T))
    return out


def _loss_sum(cfg, traffic, quant, rest, stacked, x, y):
    """Sum over the rows of x of (1/L) sum_i w_i CE_i (the caller divides
    by the number of rows)."""
    xt, x0 = x
    labels, weights = y
    b, seq = xt.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps, theta = cfg["head_dim"], cfg["rms_norm_eps"], cfg["rope_theta"]
    block, chunk = traffic["block_length"], traffic["reference"]["query_chunk"]
    t = 2 * seq
    positions = jnp.arange(t) % seq
    h = rest["embed.weight"][jnp.concatenate([xt, x0], axis=1)]

    def dense(v, w):
        return _round(v, quant) @ _round(w, quant).T

    @jax.checkpoint
    def layer(h, lw):
        a = _rms(h, lw["input_norm.gamma"], eps)

        def head_rows(name, n, norm):
            v = dense(a, lw["attention.%s_proj.weight" % name]) \
                .reshape(b, t, n, d)
            return _rope(_rms(v, lw["attention.%s_norm.gamma" % name], eps),
                         positions, theta) if norm else v

        o = _attention(head_rows("query", heads, True),
                       head_rows("key", kv_heads, True),
                       head_rows("value", kv_heads, False), seq, block, chunk)
        h = h + dense(o, lw["attention.out_proj.weight"])
        a = _rms(h, lw["post_norm.gamma"], eps).reshape(b * t, -1)
        m = _moe(cfg, quant, a, lw["moe.gate"], lw["moe.w1"], lw["moe.wg"],
                 lw["moe.w2"], traffic["reference"]["position_chunk"])
        return h + m.reshape(h.shape), None

    h, _ = jax.lax.scan(layer, h, stacked)
    @jax.checkpoint
    def row_loss(args):        # one row's logits at a time (memory only)
        h_row, labels_row, weights_row = args
        logits = dense(_rms(h_row, rest["norm.gamma"], eps),
                       rest["head.weight"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels_row[:, None], axis=-1)[:, 0]
        return jnp.mean(nll * weights_row)

    return jnp.sum(jax.lax.map(row_loss, (h[:, :seq], labels, weights)))


def _adam(opt, t, w, g, m, v):
    b1, b2 = opt["beta1"], opt["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["epsilon"])
    return w - opt["learning_rate"] * step, m, v


def _sq(tree, keep_axis0):
    return {n: jnp.sum(jnp.square(a), axis=tuple(range(int(keep_axis0), a.ndim)))
            for n, a in tree.items()}


def _pack(w, n_layers):
    """(everything outside the layers, the layers stacked for the scan)."""
    return ({n: a for n, a in w.items() if not n.startswith("layers.")},
            {short: jnp.stack([w["layers.%d.%s" % (i, short)]
                               for i in range(n_layers)])
             for short in _LAYER})


def _named(rest, stacked, leaf=float):
    """Host values under the weights' names; ``stacked`` holds one a layer."""
    out = {n: leaf(a[0] if a.ndim > 1 else a) for n, a in rest.items()}
    for short, per_layer in stacked.items():
        for i, a in enumerate(per_layer):
            out["layers.%d.%s" % (i, short)] = leaf(a)
    return out


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, traffic_json, quant, block):
    """The jitted gradient and update, one pair a configuration, traffic,
    precision and block size (a process that follows many seeds traces them
    once).  Two programs, not one, so that Adam's moments need not lie on
    the device while the gradient is computed."""
    cfg, traffic = json.loads(cfg_json), json.loads(traffic_json)
    opt = cfg["optimizer"]
    loss_sum = functools.partial(_loss_sum, cfg, traffic, quant)

    @jax.jit
    def grad(w, x, y):
        """Loss and gradient on the rows of (x, y), block by block."""
        n_rows = x[0].shape[0]
        if n_rows == block:   # one block: no second copy of the gradients
            total, g = jax.value_and_grad(loss_sum, argnums=(0, 1))(
                *w, x, y)
        else:
            blocks = jax.tree_util.tree_map(
                lambda a: a.reshape((n_rows // block, block) + a.shape[1:]),
                (x, y))

            def one(acc, xy):
                val, g = jax.value_and_grad(loss_sum, argnums=(0, 1))(
                    *w, *xy)
                return jax.tree_util.tree_map(jnp.add, acc, (val, g)), None

            zero = (jnp.float32(0),
                    jax.tree_util.tree_map(jnp.zeros_like, w))
            (total, g), _ = jax.lax.scan(one, zero, blocks)
        g = jax.tree_util.tree_map(lambda a: a / n_rows, g)
        seen = (_sq(g[0], 0), _sq(g[1], 1),
                {n: _sample(a, False) for n, a in g[0].items()},
                {n: _sample(a, True) for n, a in g[1].items()})
        return total / n_rows, seen, g

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
        raise ValueError("sdar_30b_a3b reference: precision %r" % (precision,))
    quant = None if precision == "float32" else precision
    n_layers = cfg["num_hidden_layers"]
    keys = (json.dumps(cfg, sort_keys=True),
            json.dumps(traffic, sort_keys=True), quant)

    with jax.default_matmul_precision("highest"):
        w = jax.jit(functools.partial(_pack, n_layers=n_layers))(weights)
        w0 = {n: np.asarray(a) for n, a in weights.items()}   # to the host
        weights.clear()
        moments, losses, seen = None, [], None
        for i, (x, y) in enumerate(batches):
            if rows is not None:
                x, y = jax.tree_util.tree_map(lambda a: a[:rows], (x, y))
            n_rows = x[0].shape[0]
            block = min(traffic["reference"]["block_rows"], n_rows)
            if n_rows % block:
                raise ValueError("sdar_30b_a3b reference: %d rows in blocks "
                                 "of %d" % (n_rows, block))
            grad, update = _programs(*keys, block)
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
        rest, stacked = jax.device_get(w)
    final = dict(rest)
    for short, per_layer in stacked.items():
        final.update(("layers.%d.%s" % (i, short), a)
                     for i, a in enumerate(per_layer))
    g_rest, g_st, s_rest, s_st = seen
    delta = {n: float(np.linalg.norm(
        (final[n].astype(np.float64) - w0[n]).ravel())) for n in w0}

    def norm(a):
        return float(a) ** 0.5

    return {"losses": losses, "grad_norms": _named(g_rest, g_st, norm),
            "grad_samples": _named(s_rest, s_st, lambda a: a),
            "delta_norms": delta}
