"""Model zoo (capability analog of the reference's ecosystem model repos the
BASELINE workloads come from: PaddleNLP Llama/ERNIE, PaddleClas ResNet,
PaddleRec DeepFM)."""

from .deepfm import DeepFM, deepfm_criteo  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForSequenceClassification, BertModel,
    bert_base, bert_tiny,
)
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, StaticKVCache,
    sample_next_tokens, llama_1b, llama_7b, llama_13b, llama_125m,
    llama_small, llama_tiny,
)
from .mimo_v2 import (  # noqa: F401
    MiMoV2Config, MiMoV2ForCausalLM, mimo_v2_tiny,
)
from .joyai_flash import (  # noqa: F401
    JoyAIFlashConfig, JoyAIFlashForCausalLM, joyai_flash_tiny,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig, NemotronHForCausalLM, nemotron_h_tiny,
)
from .qwen3_next import (  # noqa: F401
    Qwen3NextConfig, Qwen3NextForCausalLM, qwen3_next_tiny,
)
