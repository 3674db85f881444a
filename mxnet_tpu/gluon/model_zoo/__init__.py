"""Model zoo (reference python/mxnet/gluon/model_zoo/)."""
from . import bert, laguna, language_model, sdar, vision
from .bert import BERTForPretraining, BERTModel, bert_12_768_12, \
    bert_24_1024_16, get_bert
from .language_model import StandardRNNLM, TransformerLM, gpt_lm, \
    standard_lstm_lm_200, standard_lstm_lm_650, standard_lstm_lm_1500
from .laguna import LagunaForCausalLM, next_token_loss
from .sdar import SDARMoE, block_diffusion_loss
from .vision import get_model

__all__ = ["vision", "bert", "language_model", "sdar", "laguna",
           "get_model", "get_bert", "SDARMoE", "block_diffusion_loss",
           "LagunaForCausalLM", "next_token_loss",
           "BERTModel", "BERTForPretraining", "bert_12_768_12",
           "bert_24_1024_16", "StandardRNNLM", "TransformerLM", "gpt_lm",
           "standard_lstm_lm_200", "standard_lstm_lm_650",
           "standard_lstm_lm_1500"]
