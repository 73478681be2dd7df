"""Model library: transformer core + architecture wrappers.

Reference: ``megatron/model/`` — ``ParallelTransformer`` and friends plus
GPT/Llama/Falcon/Mistral wrapper classes that assert architecture flags.
"""

from megatron_llm_tpu.models.gpt import GPTModel
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.models.falcon import FalconModel, falcon_config
from megatron_llm_tpu.models.mistral import MistralModel, mistral_config
from megatron_llm_tpu.models.mixtral import MixtralModel, mixtral_config
from megatron_llm_tpu.models.olmoe import OlmoeModel, olmoe_config
from megatron_llm_tpu.models.keye import KeyeModel, keye_config
from megatron_llm_tpu.models.mellum import MellumModel, mellum_config
from megatron_llm_tpu.models.kanana import KananaModel, kanana_config
from megatron_llm_tpu.models.glm5 import Glm5Model, glm5_config
from megatron_llm_tpu.models.trinity import TrinityModel, trinity_config
from megatron_llm_tpu.models.lfm2 import Lfm2Model, lfm2_config
from megatron_llm_tpu.models.brumby import BrumbyModel, brumby_config
from megatron_llm_tpu.models.ouro import OuroModel, ouro_config
from megatron_llm_tpu.models.qwen2 import Qwen2Model, qwen2_config
from megatron_llm_tpu.models.gemma import GemmaModel, gemma_config
from megatron_llm_tpu.models.gpt_neox import GPTNeoXModel, gpt_neox_config
from megatron_llm_tpu.models.gpt2 import gpt2_config
from megatron_llm_tpu.models.bert import BertModel, bert_config
from megatron_llm_tpu.models.t5 import T5Model, t5_config
from megatron_llm_tpu.models.classification import (
    ClassificationModel,
    MultipleChoiceModel,
)

def _granite(cfg):
    """The Granite hybrid, imported when first built: a process that
    serves another family pays nothing for it at start-up."""
    from megatron_llm_tpu.models.granite import GraniteModel

    return GraniteModel(cfg)


def _nemotron_h(cfg):
    """The Nemotron-H hybrid, imported when first built, as Granite."""
    from megatron_llm_tpu.models.nemotron_h import NemotronHModel

    return NemotronHModel(cfg)


def _qwen3_next(cfg):
    """The Qwen3-Next hybrid, imported when first built, as Granite."""
    from megatron_llm_tpu.models.qwen3_next import Qwen3NextModel

    return Qwen3NextModel(cfg)


MODEL_REGISTRY = {
    "gpt": GPTModel,
    "llama": LlamaModel,
    "llama2": LlamaModel,
    "llama3": LlamaModel,
    "codellama": LlamaModel,
    "falcon": FalconModel,
    "mistral": MistralModel,
    "mixtral": MixtralModel,
    "olmoe": OlmoeModel,
    "keye": KeyeModel,
    "mellum": MellumModel,
    "kanana": KananaModel,
    "glm5": Glm5Model,
    "trinity": TrinityModel,
    "granite": _granite,
    "nemotron_h": _nemotron_h,
    "lfm2": Lfm2Model,
    "brumby": BrumbyModel,
    "ouro": OuroModel,
    "qwen3_next": _qwen3_next,
    "qwen2": Qwen2Model,
    "gemma": GemmaModel,
    "gpt_neox": GPTNeoXModel,
    "pythia": GPTNeoXModel,
}
# BERT/T5 train through their own entry points (pretrain_bert.py /
# pretrain_t5.py), mirroring the reference; they are not finetune.py models.
