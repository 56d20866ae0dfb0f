"""Model zoo.

Reference parity: the reference ships models in two places —
``python/paddle/vision/models`` (ResNet/VGG/MobileNet/..., SURVEY §2.2) and
the PaddleNLP-side GPT/BERT/ERNIE configs the BASELINE targets. Here both
families live under ``paddle_tpu.models`` (vision re-exports them at
``paddle_tpu.vision.models``).
"""
from . import bert  # noqa: F401
from . import ernie  # noqa: F401
from . import generation  # noqa: F401
from . import gpt  # noqa: F401
from . import jamba  # noqa: F401
from . import kv_cache  # noqa: F401
from . import llama  # noqa: F401
from . import ouro  # noqa: F401
from . import ppyoloe  # noqa: F401
from . import resnet  # noqa: F401
from . import speculative  # noqa: F401
from . import xing  # noqa: F401
from . import yolo  # noqa: F401
from .bert import (BertConfig, BertForPretraining,  # noqa: F401
                   BertForSequenceClassification, BertModel, bert_base,
                   bert_tiny)
from .ernie import (ErnieConfig, ErnieForPretraining,  # noqa: F401
                    ErnieForSequenceClassification, ErnieModel,
                    ernie_3_base, ernie_tiny)
from .generation import (GenerationEngine, generate,  # noqa: F401
                         filter_logits, per_row_keys, sample_logits,
                         sample_logits_rows)
from .gpt import GPTConfig, GPTForCausalLM, GPTModel, gpt_1p3b, gpt_tiny  # noqa: F401
from .jamba import (JambaConfig, JambaForCausalLM, JambaModel,  # noqa: F401
                    jamba_tiny)
from .kv_cache import cache_nbytes, init_cache, scatter_cache_rows  # noqa: F401
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,  # noqa: F401
                    llama2_7b, llama_tiny)
from .ouro import OuroConfig, OuroForCausalLM, OuroModel, ouro_tiny  # noqa: F401
from .ppyoloe import PPYOLOE, ppyoloe_s, ppyoloe_tiny  # noqa: F401
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101, resnet152  # noqa: F401
from .speculative import SpeculativeEngine, build_draft_model  # noqa: F401
from .xing import XingConfig, XingForCausalLM, XingModel, xing_tiny  # noqa: F401
from .yolo import YOLOv3  # noqa: F401
