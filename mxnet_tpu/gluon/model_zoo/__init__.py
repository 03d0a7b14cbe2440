"""Model zoo (ref: python/mxnet/gluon/model_zoo/)."""
from . import vision
from . import bert
from . import decoder
from . import kimi_linear
from . import mellum
from . import mixer_lm
from . import ouro
from . import ssd
from . import model_store
from .model_store import get_model_file
from .bert import (BERTModel, BERTForPretrain, get_bert, bert_12_768_12,
                   bert_24_1024_16)
from .decoder import TransformerLM, LSTMLM, transformer_lm, lstm_lm
from .kimi_linear import KimiLinearLM
from .mellum import MellumLM
from .ouro import OuroLM
from .ssd import SSD, ssd_512_resnet50_v1, ssd_300_resnet34_v1

_SSD_MODELS = {"ssd_512_resnet50_v1": ssd_512_resnet50_v1,
               "ssd_300_resnet34_v1": ssd_300_resnet34_v1}

_LM_MODELS = {"transformer_lm": transformer_lm, "lstm_lm": lstm_lm,
              "kimi_linear": kimi_linear.kimi_linear,
              "mellum": mellum.mellum, "ouro": ouro.ouro}


def get_model(name, **kwargs):
    """Vision + NLP + detection model factory (ref model_zoo get_model)."""
    if name in bert._BERT_SPECS:
        return get_bert(name, **kwargs)
    if name in _SSD_MODELS:
        return _SSD_MODELS[name](**kwargs)
    if name in _LM_MODELS:
        return _LM_MODELS[name](**kwargs)
    return vision.get_model(name, **kwargs)
