"""Model zoo for the BASELINE workloads (configs 2-5)."""
from paddle_tpu.models.llama import (  # noqa: F401
    LlamaConfig, LlamaDecoderLayer, LlamaForCausalLM, LlamaModel,
    LlamaPretrainingCriterion, llama_7b_config, llama_tiny_config,
)
from paddle_tpu.models.bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertModel, bert_base_config, bert_tiny_config,
)
from paddle_tpu.models.gpt_moe import (  # noqa: F401
    GptMoeConfig, GptMoeForCausalLM, gpt_moe_tiny_config,
)
from paddle_tpu.models.gpt import (  # noqa: F401
    GptConfig, GptForCausalLM, gpt_tiny_config,
)
from paddle_tpu.models.kimi_linear import (  # noqa: F401
    KimiLinearConfig, KimiLinearForCausalLM, KimiLinearModel,
    kimi_linear_tiny_config,
)
from paddle_tpu.models.lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig, Lfm2MoeForCausalLM, Lfm2MoeModel, lfm2_moe_tiny_config,
)
from paddle_tpu.models.deepseek_v3 import (  # noqa: F401
    DeepseekV3Config, DeepseekV3ForCausalLM, DeepseekV3Model,
    deepseek_v3_tiny_config,
)
