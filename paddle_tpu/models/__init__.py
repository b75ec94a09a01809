"""Model zoo: LLaMA (flagship), LLaMA-MoE, Kimi-Linear (KDA + MLA + sigmoid
MoE), Laguna (full + sliding attention, sigmoid MoE), Brumby (power-retention
layers: a recurrent state in place of a KV cache), BERT; vision models in
paddle_tpu.vision."""
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, llama_7b, llama_small,
    shard_llama,
)
from .llama_moe import (  # noqa: F401
    LlamaMoeConfig, LlamaMoeDecoderLayer, LlamaMoeForCausalLM,
    LlamaMoeModel, shard_llama_moe,
)
from .kimi_linear import (  # noqa: F401
    KimiLinearConfig, KimiLinearForCausalLM, KimiLinearModel,
    KimiDeltaAttention, KimiMLAttention,
)
from .laguna import (  # noqa: F401
    LagunaConfig, LagunaForCausalLM, LagunaModel,
)
from .brumby import (  # noqa: F401
    BrumbyConfig, BrumbyForCausalLM, BrumbyModel,
)
from .zaya import (  # noqa: F401
    ZayaConfig, ZayaForCausalLM, ZayaModel,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForSequenceClassification, BertForMaskedLM,
    bert_base, bert_tiny,
)
from .crnn import CRNN, crnn_tiny  # noqa: F401
