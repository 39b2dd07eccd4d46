"""Model zoo: flagship training fixtures (PaddleNLP / test-fixture analogs)."""

from .ernie import (  # noqa: F401
    ERNIE_BASE,
    ERNIE_TINY,
    ErnieConfig,
    ErnieForPretraining,
    ErnieForSequenceClassification,
    ErnieModel,
    ernie_base,
    ernie_tiny,
)
from .decoder import DecoderConfig, DecoderLM  # noqa: F401
from .gpt import (  # noqa: F401
    GPT3_1p3B, GPT_TINY, GPTConfig, GPTForCausalLM, GPTModel, GPTMoEMLP,
    gpt_moe_tiny, gpt_tiny)
from .bert import (  # noqa: F401
    BERT_BASE,
    BERT_TINY,
    BertConfig,
    BertForMaskedLM,
    BertForSequenceClassification,
    BertModel,
    bert_base,
    bert_tiny,
)
