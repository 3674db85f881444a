"""Plain reference of the ``bert_base`` configuration: BERT pretraining
(Devlin et al., arXiv:1810.04805) forward, loss, gradients and Adam in
straightforward ``jax.numpy``, float32 at ``highest`` matmul precision.

It imports nothing of the program.  No kernels, no mixed precision, no
donation: the batch goes through in blocks of rows (the loss is a mean over
rows, so the gradients of the blocks add up) and the layers through a
``lax.scan`` with the layer recomputed in the backward pass, so that a step
at the timed size fits beside nothing else on the chip.

Departures from the published model, the same as the configuration's:
dropout 0; untruncated normal initialisation (made by the harness, not here).

``precision="int8"`` or ``"fp8"`` is the control of the output check: the
inputs of every dense layer and of the vocabulary decoder are rounded to 8-bit
integers, or to float8 (e4m3), with one scale a tensor (straight-through in
the backward pass), the step below bfloat16 that a later PR would be tempted
to take.  ``rows=n`` is the planted fault "part of the batch left out, the
mean taken over the rest".
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

_LAYER = ("attention.query_proj", "attention.key_proj", "attention.value_proj",
          "attention.out_proj", "ffn.ffn_1", "ffn.ffn_2")
_NORMS = ("attn_ln", "ffn_ln")


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


def _dense(x, w, b, quant):
    return _round(x, quant) @ _round(w, quant).T + b


def _sample(a, keep_axis0):
    """At most SAMPLE evenly spaced elements of a leaf (of each layer's, for
    a stacked leaf), flattened: enough to tell a difference's size."""
    a = a.reshape((a.shape[0], -1) if keep_axis0 else (1, -1))
    return a[:, ::max(1, a.shape[1] // SAMPLE)][:, :SAMPLE]


def _ln(x, g, b, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _gelu(x):  # the tanh form, as google-research/bert's modeling.py
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _stack(w, n_layers):
    """Per-layer weights stacked on a leading axis, for the scan."""
    out = {}
    for short in [d + s for d in _LAYER for s in (".weight", ".bias")] + \
            [n + s for n in _NORMS for s in (".gamma", ".beta")]:
        out[short] = jnp.stack([w["bert.encoder.layers.%d.%s" % (i, short)]
                                for i in range(n_layers)])
    return out


def _pack(w, n_layers):
    """(everything outside the layers, the layers stacked)."""
    return ({n: a for n, a in w.items() if ".layers." not in n},
            _stack(w, n_layers))


def _named(rest, stacked, leaf=float):
    """Host values under the weights' names; ``stacked`` holds one a layer."""
    out = {n: leaf(a[0] if a.ndim > 1 else a) for n, a in rest.items()}
    for short, per_layer in stacked.items():
        for i, a in enumerate(per_layer):
            out["bert.encoder.layers.%d.%s" % (i, short)] = leaf(a)
    return out


def _loss_sum(cfg, quant, rest, stacked, x, y):
    """Sum over the rows of x of the MLM mean-per-slot and NSP terms (the
    caller divides by the number of rows)."""
    tokens, types, positions = x
    labels, nsp_labels = y
    heads, eps = cfg["num_attention_heads"], cfg["layer_norm_eps"]
    b, t = tokens.shape
    c = cfg["hidden_size"]
    d = c // heads
    h = rest["bert.word_embed.weight"][tokens] \
        + rest["bert.token_type_embed.weight"][types] \
        + rest["bert.pos_embed.embed.weight"][:t][None]
    h = _ln(h, rest["bert.embed_ln.gamma"], rest["bert.embed_ln.beta"], eps)

    @jax.checkpoint
    def layer(h, lw):
        def proj(name, v):
            return _dense(v, lw[name + ".weight"], lw[name + ".bias"], quant)

        def split(v):
            return v.reshape(b, t, heads, d).transpose(0, 2, 1, 3)

        q, k, v = (split(proj("attention." + n, h))
                   for n in ("query_proj", "key_proj", "value_proj"))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        a = a.transpose(0, 2, 1, 3).reshape(b, t, c)
        h = _ln(h + proj("attention.out_proj", a),
                lw["attn_ln.gamma"], lw["attn_ln.beta"], eps)
        f = proj("ffn.ffn_2", _gelu(proj("ffn.ffn_1", h)))
        return _ln(h + f, lw["ffn_ln.gamma"], lw["ffn_ln.beta"], eps), None

    h, _ = jax.lax.scan(layer, h, stacked)
    pooled = jnp.tanh(_dense(h[:, 0], rest["bert.pooler.weight"],
                             rest["bert.pooler.bias"], quant))
    slots = jnp.take_along_axis(h, positions[..., None], axis=1)
    slots = _ln(_gelu(_dense(slots, rest["mlm_transform.weight"],
                             rest["mlm_transform.bias"], quant)),
                rest["mlm_ln.gamma"], rest["mlm_ln.beta"], eps)
    emb = rest["bert.word_embed.weight"]
    logp = jax.nn.log_softmax(
        _round(slots, quant) @ _round(emb, quant).T, axis=-1)
    mlm = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    nlogp = jax.nn.log_softmax(
        _dense(pooled, rest["nsp_classifier.weight"],
               rest["nsp_classifier.bias"], quant), axis=-1)
    nsp = -jnp.take_along_axis(nlogp, nsp_labels[:, None], axis=-1)[..., 0]
    return jnp.sum(jnp.mean(mlm, axis=-1)) + jnp.sum(nsp)


def _adam(opt, t, w, g, m, v):
    b1, b2 = opt["beta1"], opt["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["epsilon"])
    return w - opt["learning_rate"] * step, m, v


def _sq(tree, keep_axis0):
    return {n: jnp.sum(jnp.square(a), axis=tuple(range(int(keep_axis0), a.ndim)))
            for n, a in tree.items()}


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, quant, block):
    """The jitted step and the change's norms, one pair a configuration,
    precision and block size (a process that follows many seeds traces them
    once)."""
    cfg = json.loads(cfg_json)
    opt = cfg["optimizer"]
    loss_sum = functools.partial(_loss_sum, cfg, quant)

    @jax.jit
    def step(t, w, m, v, x, y):
        """One step on the rows of (x, y), block by block."""
        n_rows = x[0].shape[0]
        blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((n_rows // block, block) + a.shape[1:]),
            (x, y))

        def one(acc, xy):
            val, g = jax.value_and_grad(loss_sum, argnums=(0, 1))(*w, *xy)
            return jax.tree_util.tree_map(jnp.add, acc, (val, g)), None

        zero = (jnp.float32(0), jax.tree_util.tree_map(jnp.zeros_like, w))
        (total, g), _ = jax.lax.scan(one, zero, blocks)
        g = jax.tree_util.tree_map(lambda a: a / n_rows, g)
        leaves, treedef = jax.tree_util.tree_flatten(w)
        new = [_adam(opt, t, *a) for a in zip(
            leaves, *(jax.tree_util.tree_leaves(s) for s in (g, m, v)))]
        w, m, v = (treedef.unflatten([o[i] for o in new]) for i in range(3))
        seen = (_sq(g[0], 0), _sq(g[1], 1),
                {n: _sample(a, False) for n, a in g[0].items()},
                {n: _sample(a, True) for n, a in g[1].items()})
        return total / n_rows, seen, w, m, v

    @jax.jit
    def change(w, w0):
        d = jax.tree_util.tree_map(jnp.subtract, w, w0)
        return _sq(d[0], 0), _sq(d[1], 1)

    return step, change


def run(cfg, traffic, weights, batches, precision="float32", rows=None):
    """Follow ``len(batches)`` training steps from ``weights``.

    Returns ``{"losses": [...], "grad_norms": {name: norm of the first
    step's gradient}, "grad_samples": {name: SAMPLE of its elements},
    "delta_norms": {name: norm of the weights' change over all the
    steps}}`` on the host.
    """
    if precision not in ("float32", "int8", "fp8"):
        raise ValueError("bert_base reference: precision %r" % (precision,))
    quant = None if precision == "float32" else precision
    n_layers = cfg["num_hidden_layers"]
    block = traffic["reference"]["block_rows"]
    step, change = _programs(json.dumps(cfg, sort_keys=True), quant, block)

    with jax.default_matmul_precision("highest"):
        w0 = jax.jit(functools.partial(_pack, n_layers=n_layers))(
            dict(weights))
        w = w0
        m = v = jax.tree_util.tree_map(jnp.zeros_like, w0)
        losses, seen = [], None
        for i, (x, y) in enumerate(batches):
            if rows is not None:
                x, y = jax.tree_util.tree_map(lambda a: a[:rows], (x, y))
            if x[0].shape[0] % block:
                raise ValueError("bert_base reference: %d rows in blocks of "
                                 "%d" % (x[0].shape[0], block))
            loss, new, w, m, v = step(jnp.float32(i + 1), w, m, v, x, y)
            losses.append(float(loss))
            seen = new if i == 0 else seen
        delta_sq = change(w, w0)
    (g_rest, g_st, s_rest, s_st), (d_rest, d_st) = jax.device_get(
        (seen, delta_sq))

    def norm(a):
        return float(a) ** 0.5

    return {"losses": losses, "grad_norms": _named(g_rest, g_st, norm),
            "grad_samples": _named(s_rest, s_st, lambda a: a),
            "delta_norms": _named(d_rest, d_st, norm)}
