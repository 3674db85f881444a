"""Builder of the ``bert_base`` configuration: weights and batches from a
key, the program's trainer, and the count of model operations.

Only ``make_trainer`` touches the program (``mxnet_tpu``).  Weights are
named as the program names its parameters, because that is how they are
handed to it; the plain reference reads the same dict by the same names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

UNIT = "tokens"


def n_mask(cfg, traffic):
    return max(1, int(traffic["seq"] * traffic["mask_fraction"]))


def weight_shapes(cfg):
    """{program parameter name: (shape, kind)}; kind is how it is drawn."""
    c, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "bert.word_embed.weight": ((v, c), "normal"),
        "bert.token_type_embed.weight": ((cfg["type_vocab_size"], c), "normal"),
        "bert.pos_embed.embed.weight":
            ((cfg["max_position_embeddings"], c), "normal"),
    }

    def dense(name, n_out, n_in):
        out[name + ".weight"] = ((n_out, n_in), "normal")
        out[name + ".bias"] = ((n_out,), "zeros")

    def norm(name):
        out[name + ".gamma"] = ((c,), "ones")
        out[name + ".beta"] = ((c,), "zeros")

    norm("bert.embed_ln")
    for i in range(cfg["num_hidden_layers"]):
        p = "bert.encoder.layers.%d." % i
        for proj in ("query_proj", "key_proj", "value_proj", "out_proj"):
            dense(p + "attention." + proj, c, c)
        norm(p + "attn_ln")
        dense(p + "ffn.ffn_1", ff, c)
        dense(p + "ffn.ffn_2", c, ff)
        norm(p + "ffn_ln")
    dense("bert.pooler", c, c)
    dense("mlm_transform", c, c)
    norm("mlm_ln")
    dense("nsp_classifier", 2, c)
    return out


def make_weights(cfg, key):
    """All weights in float32 (the masters the optimizer keeps) from one
    key; traced inside the harness's one jitted set-up call."""
    std = cfg["initializer_range"]
    out = {}
    for i, (name, (shape, kind)) in enumerate(weight_shapes(cfg).items()):
        if kind == "normal":
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        else:
            out[name] = jnp.full(shape, 1.0 if kind == "ones" else 0.0,
                                 jnp.float32)
    return out


def make_batch(cfg, traffic, key):
    """One batch ``(x, y)``: every row differs.  x = (tokens, types,
    masked positions, sorted, without repeats), y = (masked labels, NSP)."""
    b, t, v = traffic["batch"], traffic["seq"], cfg["vocab_size"]
    m = n_mask(cfg, traffic)
    k = jax.random.split(key, 5)
    tokens = jax.random.randint(k[0], (b, t), 0, v, jnp.int32)
    types = jax.random.randint(k[1], (b, t), 0, 2, jnp.int32)
    # m distinct positions a row: the m smallest of t random draws
    order = jnp.argsort(jax.random.uniform(k[2], (b, t)), axis=1)
    positions = jnp.sort(order[:, :m], axis=1).astype(jnp.int32)
    labels = jax.random.randint(k[3], (b, m), 0, v, jnp.int32)
    nsp = jax.random.randint(k[4], (b,), 0, 2, jnp.int32)
    return (tokens, types, positions), (labels, nsp)


def ops_per_step(cfg, traffic):
    """Model operations of one training step (multiply-adds x 2; forward
    x 3 for forward, input gradients and weight gradients; nothing
    recomputed).  The MLM transform and the vocabulary decoder run on the
    masked slots only.  Copied from bench.py:bert_train_flops_per_step."""
    c, ff = cfg["hidden_size"], cfg["intermediate_size"]
    b, t = traffic["batch"], traffic["seq"]
    per_tok = cfg["num_hidden_layers"] * (8 * c * c + 4 * t * c + 4 * c * ff)
    per_masked = 2 * c * c + 2 * c * cfg["vocab_size"]
    return 3.0 * (per_tok * b * t + per_masked * b * n_mask(cfg, traffic))


def units_per_step(cfg, traffic):
    return traffic["batch"] * traffic["seq"]


def attention_shape(cfg, traffic, chips):
    """(batch x heads on one chip, T, D) of one attention call."""
    h = cfg["num_attention_heads"]
    return (traffic["batch"] // chips * h, traffic["seq"],
            cfg["hidden_size"] // h)


def make_trainer(cfg, weights, mesh):
    """The program under test: BERTForPretraining under FusedTrainer, as
    bench.py and chip_smoke.py build it, holding the harness's weights."""
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo import bert as bert_zoo

    class PretrainStep(HybridBlock):
        def __init__(self):
            super().__init__()
            self.model = bert_zoo.BERTForPretraining(
                vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
                hidden_size=cfg["intermediate_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                max_length=cfg["max_position_embeddings"],
                token_type_vocab_size=cfg["type_vocab_size"],
                dropout=cfg["hidden_dropout_prob"],
                layer_norm_eps=cfg["layer_norm_eps"])

        def forward(self, tokens, types, positions):
            return self.model(tokens, types, valid_length=None,
                              masked_positions=positions)

    def pretrain_loss(outs, masked_labels, nsp_labels):
        mlm_scores, nsp_scores = outs
        logp = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(
            logp, masked_labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        nlogp = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), axis=-1)
        nsp = jnp.take_along_axis(
            nlogp, nsp_labels[:, None].astype(jnp.int32), axis=-1)[..., 0]
        return -jnp.mean(ll) - jnp.mean(nsp)

    net = PretrainStep()
    params = net.collect_params()
    if set(params) != {"model." + n for n in weights}:
        raise SystemExit("bert_base: the program's parameters are not the "
                         "ones this builder makes: %s" % sorted(
                             set(params) ^ {"model." + n for n in weights})[:6])
    for name, p in params.items():
        p.set_data(weights[name[len("model."):]])
    opt = dict(cfg["optimizer"])
    return parallel.FusedTrainer(
        net, loss_fn=pretrain_loss, optimizer=opt.pop("name"),
        optimizer_params=opt, dtype=cfg["compute_dtype"], mesh=mesh)


def program_names(weights):
    """weight name -> the trainer's parameter name."""
    return {n: "model." + n for n in weights}


def first_gradient(cfg, state_leaf):
    """The gradient the optimizer was given in step 1, from its state after
    that step: Adam's first moment is (1 - beta1) * g."""
    m, _v = state_leaf
    return m / (1.0 - cfg["optimizer"]["beta1"])
