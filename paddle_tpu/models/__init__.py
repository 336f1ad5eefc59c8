"""In-repo model zoo for the benchmark configs (BASELINE.json; the reference
keeps these in PaddleNLP — minimal equivalents live here per SURVEY.md §2.3)."""

from .llama import LlamaConfig, LlamaForCausalLM, LlamaModel  # noqa: F401
from .deepseek_v32 import DeepseekV32Config, DeepseekV32ForCausalLM  # noqa: F401
from .ling3 import Ling3Config, Ling3ForCausalLM  # noqa: F401
from .kimi_linear import KimiLinearConfig, KimiLinearForCausalLM  # noqa: F401
from .mellum2 import Mellum2Config, Mellum2ForCausalLM  # noqa: F401
from .lfm2_moe import Lfm2MoeConfig, Lfm2MoeForCausalLM  # noqa: F401
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTForCausalLMPipe,
    GPTForPretraining,
    GPTModel,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertForMaskedLM,
    BertForQuestionAnswering,
    BertForSequenceClassification,
    BertModel,
)
