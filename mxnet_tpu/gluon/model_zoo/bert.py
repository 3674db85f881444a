"""BERT model family (the BASELINE.md config-3 pretraining target).

Reference anchors: the reference framework itself ships only the transformer
attention kernels (src/operator/contrib/transformer.cc) — the BERT model
lived downstream in gluon-nlp built on those ops.  Here the family is
in-tree, built on nn.TransformerEncoder, so the pretraining benchmark is
self-contained.  All Dense/Embedding weights carry tensor-parallel sharding
hints, so the same model runs single-chip or pjit-sharded (dp×tp) unchanged.
"""
from __future__ import annotations

from ... import ndarray as nd
from ...base import MXNetError
from .. import nn
from ..block import HybridBlock

__all__ = ["BERTModel", "BERTForPretraining", "bert_12_768_12",
           "bert_24_1024_16", "get_bert"]


class BERTModel(HybridBlock):
    """BERT encoder: embeddings (word + position + token-type) -> LN ->
    dropout -> TransformerEncoder -> (sequence output, pooled output)."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 token_type_vocab_size=2, dropout=0.1, use_pooler=True,
                 layer_norm_eps=1e-12, **kwargs):
        super().__init__()
        self._units = units
        self.word_embed = nn.Embedding(vocab_size, units)
        self.token_type_embed = nn.Embedding(token_type_vocab_size, units)
        self.pos_embed = nn.PositionalEmbedding(max_length, units)
        self.embed_ln = nn.LayerNorm(epsilon=layer_norm_eps,
                                     in_channels=units)
        self.embed_dropout = nn.Dropout(dropout) if dropout else None
        self.encoder = nn.TransformerEncoder(
            num_layers, units, hidden_size, num_heads, dropout=dropout,
            attention_dropout=dropout, activation="gelu",
            layer_norm_eps=layer_norm_eps)
        self.pooler = (nn.Dense(units, activation="tanh", flatten=False,
                                in_units=units)
                       if use_pooler else None)

    def forward(self, inputs, token_types=None, valid_length=None):
        """inputs: (B, T) int token ids; token_types: (B, T);
        valid_length: (B,) unpadded lengths -> attention mask."""
        x = self.word_embed(inputs)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = self.pos_embed(x)
        x = self.embed_ln(x)
        if self.embed_dropout is not None:
            x = self.embed_dropout(x)
        mask = None
        if valid_length is not None:
            T = inputs.shape[1]
            # (B, 1, 1, Tk) key-padding mask, broadcast over heads and Tq
            steps = nd.arange(T)
            mask = (steps.reshape((1, 1, 1, T)) <
                    valid_length.reshape((-1, 1, 1, 1)))
        seq = self.encoder(x, mask=mask)
        if self.pooler is None:
            return seq
        pooled = self.pooler(seq[:, 0, :])
        return seq, pooled


class BERTForPretraining(HybridBlock):
    """MLM + NSP heads over BERTModel; returns (mlm_scores, nsp_scores)."""

    def __init__(self, bert=None, vocab_size=30522, tie_weights=True,
                 layer_norm_eps=1e-12, **bert_kwargs):
        super().__init__()
        self.bert = bert if bert is not None else BERTModel(
            vocab_size=vocab_size, **bert_kwargs)
        if self.bert.pooler is None:
            raise MXNetError("BERTForPretraining needs the NSP pooled "
                             "output; build the backbone with "
                             "use_pooler=True")
        self._vocab_size = vocab_size
        self._tie = tie_weights
        units = self.bert._units
        self.mlm_transform = nn.Dense(units, activation="gelu",
                                      flatten=False, in_units=units)
        self.mlm_ln = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        if not tie_weights:
            self.mlm_decoder = nn.Dense(vocab_size, flatten=False,
                                        in_units=units)
        self.nsp_classifier = nn.Dense(2, flatten=False, in_units=units)

    def forward(self, inputs, token_types=None, valid_length=None,
                masked_positions=None):
        seq, pooled = self.bert(inputs, token_types, valid_length)
        h = seq
        if masked_positions is not None:
            # gather only masked slots: (B, M, C).  The index stays
            # (B, M, 1): one slice per slot is a whole row of C.  Broadcast
            # to (B, M, C) it would gather, and scatter-add in the
            # backward, B*M*C single elements.
            h = nd.take_along_axis(
                seq, masked_positions.astype("int32").expand_dims(-1),
                axis=1)
        h = self.mlm_ln(self.mlm_transform(h))
        if self._tie:
            emb = self.bert.word_embed.weight.data()  # (V, C)
            mlm_scores = nd.dot(h.reshape((-1, h.shape[-1])), emb.T) \
                .reshape(h.shape[:-1] + (self._vocab_size,))
        else:
            mlm_scores = self.mlm_decoder(h)
        nsp_scores = self.nsp_classifier(pooled)
        return mlm_scores, nsp_scores


def pretraining_loss(mlm_scores, nsp_scores, masked_labels, masked_weights,
                     nsp_labels):
    """Standard BERT pretraining loss (masked-LM CE + NSP CE) on NDArrays."""
    logp = nd.log_softmax(mlm_scores, axis=-1)
    mlm_ll = nd.pick(logp, masked_labels, axis=-1)
    denom = nd.sum(masked_weights) + 1e-6
    mlm_loss = -nd.sum(mlm_ll * masked_weights) / denom
    nsp_logp = nd.log_softmax(nsp_scores, axis=-1)
    nsp_loss = -nd.mean(nd.pick(nsp_logp, nsp_labels, axis=-1))
    return mlm_loss + nsp_loss


_BERT_CONFIGS = {
    "bert_12_768_12": dict(units=768, hidden_size=3072, num_layers=12,
                           num_heads=12),
    "bert_24_1024_16": dict(units=1024, hidden_size=4096, num_layers=24,
                            num_heads=16),
}


def get_bert(name, vocab_size=30522, pretraining=False, **kwargs):
    if name not in _BERT_CONFIGS:
        raise MXNetError("unknown bert config %r (have %s)"
                         % (name, sorted(_BERT_CONFIGS)))
    cfg = dict(_BERT_CONFIGS[name])
    cfg.update(kwargs)
    if pretraining:
        return BERTForPretraining(vocab_size=vocab_size, **cfg)
    return BERTModel(vocab_size=vocab_size, **cfg)


def bert_12_768_12(**kwargs):
    """BERT-base."""
    return get_bert("bert_12_768_12", **kwargs)


def bert_24_1024_16(**kwargs):
    """BERT-large."""
    return get_bert("bert_24_1024_16", **kwargs)
