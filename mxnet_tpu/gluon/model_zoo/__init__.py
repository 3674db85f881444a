"""Model zoo (reference python/mxnet/gluon/model_zoo/): the vision models
(``vision``), BERT (``bert``), RNN and transformer language models
(``language_model``), and three mixture-of-experts decoders:
``sdar.SDARMoE`` (Qwen3-MoE's layer trained by block diffusion),
``laguna.LagunaForCausalLM`` (full and sliding-window layers, sigmoid
router, shared expert) and ``glm_moe_lite.GlmMoeLiteForCausalLM``
(GLM-4.7-Flash: latent attention, a selection bias in the router, a
multi-token prediction module)."""
from . import bert, glm_moe_lite, laguna, language_model, sdar, vision
from .bert import BERTForPretraining, BERTModel, bert_12_768_12, \
    bert_24_1024_16, get_bert
from .language_model import StandardRNNLM, TransformerLM, gpt_lm, \
    standard_lstm_lm_200, standard_lstm_lm_650, standard_lstm_lm_1500
from .glm_moe_lite import GlmMoeLiteForCausalLM, mtp_loss
from .laguna import LagunaForCausalLM, next_token_loss
from .sdar import SDARMoE, block_diffusion_loss
from .vision import get_model

__all__ = ["vision", "bert", "language_model", "sdar", "laguna",
           "glm_moe_lite", "get_model", "get_bert", "SDARMoE",
           "block_diffusion_loss", "LagunaForCausalLM", "next_token_loss",
           "GlmMoeLiteForCausalLM", "mtp_loss",
           "BERTModel", "BERTForPretraining", "bert_12_768_12",
           "bert_24_1024_16", "StandardRNNLM", "TransformerLM", "gpt_lm",
           "standard_lstm_lm_200", "standard_lstm_lm_650",
           "standard_lstm_lm_1500"]
