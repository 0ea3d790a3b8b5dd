"""mx.models — modern model families (transformers).

Vision classics live in gluon.model_zoo.vision (reference layout); the
transformer families (no reference analogue) live here."""
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    LlamaStackedDecoder, llama_shardings,
                    LLAMA3_8B, LLAMA_TINY)
from .bert import (BertConfig, BertModel, BertForSequenceClassification,
                   BertForPretraining, BERT_BASE, BERT_TINY)
from .gpt import GPTConfig, GPTModel, GPT2_SMALL, GPT_TINY
from .vit import ViTConfig, ViTModel, VIT_B16, VIT_TINY
from .t5 import T5Config, T5Model, T5_SMALL, T5_TINY
from .minicpm_sala import (MiniCPMSALAConfig, MiniCPMSALAForCausalLM,
                           SALA_TINY)
from .evabyte import EvaByteConfig, EvaByteForCausalLM, EVABYTE_TINY
from .cohere2_moe import (Cohere2MoEConfig, Cohere2MoEForCausalLM,
                          COHERE2_MOE_TINY)
from .generation import generate

# attach the decode loop as a method on the causal-LM families (one
# definition; generation.py imports none of the model modules)
def _generate_method(self, input_ids, max_new_tokens, **kwargs):
    return generate(self, input_ids, max_new_tokens, **kwargs)


GPTModel.generate = _generate_method
LlamaForCausalLM.generate = _generate_method
del _generate_method

__all__ = [
    "LlamaConfig", "LlamaForCausalLM", "LlamaModel", "LlamaStackedDecoder",
    "llama_shardings",
    "LLAMA3_8B", "LLAMA_TINY",
    "BertConfig", "BertModel", "BertForSequenceClassification",
    "BertForPretraining", "BERT_BASE", "BERT_TINY",
    "GPTConfig", "GPTModel", "GPT2_SMALL", "GPT_TINY",
    "ViTConfig", "ViTModel", "VIT_B16", "VIT_TINY",
    "T5Config", "T5Model", "T5_SMALL", "T5_TINY",
    "MiniCPMSALAConfig", "MiniCPMSALAForCausalLM", "SALA_TINY",
    "EvaByteConfig", "EvaByteForCausalLM", "EVABYTE_TINY",
    "Cohere2MoEConfig", "Cohere2MoEForCausalLM", "COHERE2_MOE_TINY",
    "generate",
]
