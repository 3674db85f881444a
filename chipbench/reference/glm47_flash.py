"""Plain reference of the ``glm47_flash`` configuration: training of
GLM-4.7-Flash's decoder (``glm4_moe_lite``: multi-head latent attention in
every layer, a leading dense layer, sigmoid-routed small experts chosen
under a selection bias beside a shared expert, one multi-token prediction
module that shares the embedding and the head) forward, the loss of two
terms, gradients and Adam in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision.

It imports nothing of the program.  No kernels, no mixed precision, no
sorting of positions: attention goes by chunks of queries against ALL keys
of every head (keys and values expanded from the latent, a head at its
full 256 dimensions); the experts are a loop over the ones held, each
applied to all positions and weighted by its routing weight or 0; the
shared expert is one more product on every position.  The sparse layers go
through a ``lax.scan`` with the layer recomputed in the backward pass, the
prediction module is recomputed likewise, and both losses go through chunks
of positions, so that a step at the timed size fits once the trainer is
freed.  For the same reason ``run`` EMPTIES the ``weights`` dict it is
given (the float32 originals go to the host for the final comparison), and
Adam's moments wait on the host while a gradient is computed (gradient and
update are two programs; the update donates its state).

The equations (DeepSeek-V2 arXiv:2405.04434 section 2.1 for the attention,
DeepSeek-V3 arXiv:2412.19437 sections 2.1.2 and 2.2 for the router and the
prediction module), on a layer's normed input ``a``:

    c_q = RMSNorm(a W_qa)           q = c_q W_qb        (a head: nope + rope)
    [c_kv ; k_r] = a W_kva          c_kv = RMSNorm(c_kv)
    [k_nope ; v] = c_kv W_kvb       (a head: nope + v)
    q_h = [q_nope_h ; rope(q_rope_h)]   k_h = [k_nope_h ; rope(k_r)]
    o_h = softmax(q_h k_h^T / sqrt(nope + rope) + causal) v_h
    s = sigmoid(m W_r); the k experts with the largest s + bias;
    w_e = scale * s_e / sum of the chosen s
    h' = [RMSNorm_h(h) ; RMSNorm_e(Emb(next token))] W_eh, one sparse layer,
    RMSNorm, the main model's head
    loss = CE(main, token t + 1) + lambda CE(module, token t + 2)

Readings of what the published config leaves open, the same as the
configuration's ``assumed`` (each a departure from, or a choice within, the
published description): the rotary is the rotate-half form (the
checkpoint's interleaved form is a fixed permutation of weight columns);
the module reads the main model's hidden states AFTER the final norm and
concatenates hidden before embedding; lambda is 0.1; the selection bias is
a seeded constant that no step updates and no gradient reaches; no
auxiliary loss, no expert groups.  The share of one chip under expert
parallelism: the router is as wide as published, and only the held experts'
parts of the result are added; a slice of the vocabulary.

``precision="int8"`` or ``"fp8"`` is the control of the output check: the
inputs of every dense layer (the latent block's five, ``W_eh``), of every
expert's three products (the shared expert's too) and of the head are
rounded to 8-bit integers, or to float8 (e4m3), with one scale a tensor
(straight-through in the backward pass); the router's product stays in
float32, as a PR taking that step would leave it.  ``rows=n`` is the
planted fault "part of the batch left out, the mean taken over the rest":
the first ``n`` sequences are kept; ``n = 0`` (half of a batch of ONE
sequence) keeps the first half of that sequence's positions.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE = 4096  # elements of a leaf's gradient kept for the difference
# leaves that no gradient reaches and no step moves
FROZEN = "moe.select_bias"


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


def _rope(x, theta):
    """Rotate-half rotary positions 0..T-1 over ALL the dimensions of
    x (b, t, heads, r): pair (i, i + r/2) turns by ``t theta**(-2i/r)``."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    rot = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + rot * sin


def _attention(q, k, v, chunk):
    """q, k, v (b, T, H, d) -> (b, T, H, d); causal softmax over the keys
    ``j <= i``, ``chunk`` queries at a time."""
    b, t, heads, d = q.shape
    chunk = min(chunk, t)
    if t % chunk:
        raise ValueError("glm47_flash reference: %d queries in chunks of %d"
                         % (t, chunk))
    qc = q.reshape(b, t // chunk, chunk, heads, d).transpose(1, 0, 2, 3, 4)
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one(args):
        qi, start = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / jnp.sqrt(float(d))
        i = start + jnp.arange(chunk)[:, None]
        s = jnp.where(j <= i, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, (qc, jnp.arange(t // chunk) * chunk))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, heads, d)


def _latent_attention(cfg, quant, chunk, a, lw):
    """Multi-head latent attention, expanded form, on the normed input
    ``a`` (b, t, c); ``lw``: the layer's ``attention.*`` weights."""
    b, t, _ = a.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    rkv, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dense = functools.partial(_dense, quant=quant)
    c_q = _rms(dense(a, lw["attention.q_a_proj.weight"]),
               lw["attention.q_a_norm.gamma"], eps)
    q = dense(c_q, lw["attention.q_b_proj.weight"]).reshape(b, t, heads, -1)
    kv_a = dense(a, lw["attention.kv_a_proj.weight"])
    c_kv = _rms(kv_a[..., :rkv], lw["attention.kv_a_norm.gamma"], eps)
    k_r = _rope(kv_a[..., None, rkv:], cfg["rope_theta"])    # (b, t, 1, rope)
    kv = dense(c_kv, lw["attention.kv_b_proj.weight"]) \
        .reshape(b, t, heads, -1)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], cfg["rope_theta"])], -1)
    # the ONE rotary key, the same for every head
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, t, heads,
                                                k_r.shape[-1]))], -1)
    o = _attention(q, k, kv[..., nope:], chunk)
    return dense(o.reshape(b, t, -1), lw["attention.out_proj.weight"])


def _gated(x, wg, w1, w2, quant):
    """One gated SiLU expert on rounded ``x``: weights (in, hidden) twice
    and (hidden, out)."""
    a = jax.nn.silu(x @ _round(wg, quant)) * (x @ _round(w1, quant))
    return _round(a, quant) @ _round(w2, quant)


def _moe(cfg, quant, x, lw, chunk=None):
    """x (n, c): route over all the router's experts under the selection
    bias, add the held ones' weighted results and the shared expert's;
    ``chunk`` positions at a time (memory only)."""
    if chunk and x.shape[0] > chunk:
        part = jax.checkpoint(lambda xc: _moe(cfg, quant, xc, lw))
        return jax.lax.map(part, x.reshape(-1, chunk, x.shape[1])) \
            .reshape(x.shape)
    k, first = cfg["num_experts_per_tok"], cfg["first_expert"]
    gate, w1 = lw["moe.gate"], lw["moe.w1"]
    s = jax.nn.sigmoid(x @ gate.T)
    # the bias chooses; the weights are made from the scores alone
    _, top_e = jax.lax.top_k(s + lw["moe.select_bias"], k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    top_w = cfg["routed_scaling_factor"] * top_s \
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
    return out + _gated(xq, lw["moe.shared_wg"], lw["moe.shared_w1"],
                        lw["moe.shared_w2"], quant)


def _layer(cfg, traffic, quant, h, lw):
    """One pre-norm decoder layer on h (b, t, c): latent attention, then
    the dense MLP where ``lw`` holds one, else the experts."""
    eps, ref = cfg["rms_norm_eps"], traffic["reference"]
    h = h + _latent_attention(
        cfg, quant, ref["query_chunk"],
        _rms(h, lw["input_norm.gamma"], eps), lw)
    m = _rms(h, lw["post_norm.gamma"], eps).reshape(-1, h.shape[-1])
    if "mlp.gate_proj.weight" in lw:
        m = _gated(_round(m, quant), lw["mlp.gate_proj.weight"].T,
                   lw["mlp.up_proj.weight"].T, lw["mlp.down_proj.weight"].T,
                   quant)
    else:
        m = _moe(cfg, quant, m, lw, ref["position_chunk"])
    return h + m.reshape(h.shape)


def _cross_entropy_sum(quant, head, h, labels, chunk):
    """Sum over h's (n, c) positions of the cross-entropy of ``h head^T``
    against ``labels`` (n,), ``chunk`` positions' logits at a time."""
    @jax.checkpoint
    def part(args):
        h_c, labels_c = args
        logp = jax.nn.log_softmax(_dense(h_c, head, quant), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels_c[:, None],
                                            axis=-1))

    n = h.shape[0]
    chunk = min(chunk, n)
    if n % chunk:
        raise ValueError("glm47_flash reference: %d positions in chunks of "
                         "%d" % (n, chunk))
    return jnp.sum(jax.lax.map(part, (h.reshape(n // chunk, chunk, -1),
                                      labels.reshape(n // chunk, chunk))))


def _loss_sum(cfg, traffic, quant, rest, stacks, module, x, y):
    """Sum over the ``B T`` positions of the main model's cross-entropy
    plus lambda times the prediction module's (the caller divides by their
    number).  ``x`` (B, T + 1): the main model reads the first T ids, the
    module the embeddings of the last T; ``y`` (B, T + 1): the main labels
    are its first T, the module's its last T."""
    eps, chunk = cfg["rms_norm_eps"], traffic["reference"]["position_chunk"]
    layer = functools.partial(_layer, cfg, traffic, quant)
    h = rest["embed.weight"][x[:, :-1]]
    for stacked in stacks:      # the dense layers, then the sparse ones
        h, _ = jax.lax.scan(
            jax.checkpoint(lambda h, lw: (layer(h, lw), None)), h, stacked)
    # departure (a reading): the module is given the hidden states AFTER
    # the main model's final norm, and norms them again with its own gain
    h = _rms(h, rest["norm.gamma"], eps)
    main = _cross_entropy_sum(quant, rest["head.weight"],
                              h.reshape(-1, h.shape[-1]),
                              y[:, :-1].reshape(-1), chunk)

    @jax.checkpoint
    def predict(h, emb, module):
        joined = jnp.concatenate(
            [_rms(h, module["hidden_norm.gamma"], eps),
             _rms(emb, module["embed_norm.gamma"], eps)], -1)
        lw = {n[len("layer."):]: a for n, a in module.items()
              if n.startswith("layer.")}
        return _rms(layer(_dense(joined, module["proj.weight"], quant), lw),
                    module["norm.gamma"], eps)

    # the SAME embedding and, below, the SAME head as the main model
    h2 = predict(h, rest["embed.weight"][x[:, 1:]], module)
    second = _cross_entropy_sum(quant, rest["head.weight"],
                                h2.reshape(-1, h2.shape[-1]),
                                y[:, 1:].reshape(-1), chunk)
    return main + cfg["mtp_loss_weight"] * second


def _adam(opt, t, w, g, m, v):
    b1, b2 = opt["beta1"], opt["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["epsilon"])
    return w - opt["learning_rate"] * step, m, v


def layer_runs(cfg):
    """``[(first layer, layers)]``: the leading dense layers, the sparse
    layers after them; a run's layers are alike and go through one scan."""
    dense, n = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    return [(first, count) for first, count in ((0, dense),
                                                (dense, n - dense)) if count]


def _pack(w, cfg):
    """(everything outside the layers, one dict of stacked layer weights a
    run of equal layers, the prediction module's weights)."""
    rest = {n: a for n, a in w.items()
            if not n.startswith(("layers.", "mtp."))}
    stacks = []
    for first, count in layer_runs(cfg):
        prefix = "layers.%d." % first
        shorts = [n[len(prefix):] for n in w if n.startswith(prefix)]
        stacks.append({s: jnp.stack([w["layers.%d.%s" % (i, s)]
                                     for i in range(first, first + count)])
                       for s in shorts})
    module = {n[len("mtp."):]: a for n, a in w.items()
              if n.startswith("mtp.")}
    return rest, stacks, module


def _named(cfg, rest, stacks, module, leaf=float):
    """Host values under the weights' names; a stack holds one a layer."""
    out = {n: leaf(a) for n, a in rest.items()}
    for (first, _), stacked in zip(layer_runs(cfg), stacks):
        for short, per_layer in stacked.items():
            for i, a in enumerate(per_layer):
                out["layers.%d.%s" % (first + i, short)] = leaf(a)
    out.update({"mtp." + n: leaf(a) for n, a in module.items()})
    return out


def _sq(tree, keep_axis0):
    return {n: jnp.sum(jnp.square(a),
                       axis=tuple(range(int(keep_axis0), a.ndim)))
            for n, a in tree.items()}


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
        """Mean loss over the B T positions of (x, y), and its gradient
        (the embedding's and the head's are sums of two paths)."""
        positions = x.shape[0] * (x.shape[1] - 1)
        total, g = jax.value_and_grad(loss_sum, argnums=(0, 1, 2))(*w, x, y)
        g = jax.tree_util.tree_map(lambda a: a / positions, g)
        rest, stacks, module = g
        seen = (_sq(rest, 0), [_sq(s, 1) for s in stacks], _sq(module, 0),
                {n: _sample(a, False) for n, a in rest.items()},
                [{n: _sample(a, True) for n, a in s.items()}
                 for s in stacks],
                {n: _sample(a, False) for n, a in module.items()})
        return total / positions, seen, g

    def frozen(path):
        return any(getattr(k, "key", None) in (FROZEN, "layer." + FROZEN)
                   for k in path)

    @functools.partial(jax.jit, donate_argnums=(1, 3, 4))
    def update(t, w, g, m, v):
        paths, treedef = jax.tree_util.tree_flatten_with_path(w)
        new = [(a[0],) + a[2:] if frozen(path) else _adam(opt, t, *a)
               for (path, _), a in zip(paths, zip(
                   jax.tree_util.tree_leaves(w),
                   *(jax.tree_util.tree_leaves(s) for s in (g, m, v))))]
        return tuple(treedef.unflatten([o[i] for o in new])
                     for i in range(3))

    return grad, update


def run(cfg, traffic, weights, batches, precision="float32", rows=None):
    """Follow ``len(batches)`` training steps from ``weights`` (a dict,
    which this EMPTIES: see the module's docstring).

    Returns ``{"losses": [...], "grad_norms": {name: norm of the first
    step's gradient}, "grad_samples": {name: SAMPLE of its elements},
    "delta_norms": {name: norm of the weights' change over all the
    steps}}`` on the host, of the trained leaves: the selection biases
    have no gradient, no step moves them, and they stand under none.
    """
    if precision not in ("float32", "int8", "fp8"):
        raise ValueError("glm47_flash reference: precision %r" % (precision,))
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
                half = (x.shape[1] - 1) // 2 + 1
                x, y = x[:, :half], y[:, :half]
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
    norms, samples = seen[:3], seen[3:]
    def trained(named):
        return {n: a for n, a in named.items() if not n.endswith(FROZEN)}

    delta = {n: float(np.linalg.norm(
        (final[n].astype(np.float64) - w0[n]).ravel())) for n in trained(w0)}

    return {"losses": losses,
            "grad_norms": trained(_named(cfg, *norms,
                                         leaf=lambda a: float(a) ** 0.5)),
            "grad_samples": trained(_named(cfg, *samples, leaf=lambda a: a)),
            "delta_norms": delta}
