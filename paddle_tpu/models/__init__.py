"""Flagship model zoo (NLP side; vision lives in paddle_tpu.vision.models)."""
from .llama import (  # noqa: F401
    LlamaConfig, LlamaMoEConfig, LlamaModel, LlamaForCausalLM, LlamaDecoderLayer,
    llama_param_count, llama_flops_per_token, llama_moe_param_counts,
    llama_moe_flops_per_token, apply_rotary_pos_emb,
)
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, GPTAttention, GPTForCausalLMPipe,
    gpt_param_count,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining, BertForSequenceClassification,
)
from .dit import (  # noqa: F401
    DiTConfig, DiT, DiTBlock, GaussianDiffusion,
)
from .falcon_h1 import (  # noqa: F401
    FalconH1Config, FalconH1ForCausalLM, FalconH1Block,
)
from .openpangu_moe import (  # noqa: F401
    OpenPanguMoEConfig, OpenPanguMoEForCausalLM, OpenPanguMoEBlock,
)
from .laguna import (  # noqa: F401
    LagunaConfig, LagunaForCausalLM, LagunaBlock,
)
from .glm_moe_dsa import (  # noqa: F401
    GlmMoeDsaConfig, GlmMoeDsaForCausalLM, GlmMoeDsaBlock,
)
from .dots3_note import (  # noqa: F401
    Dots3NoteConfig, Dots3NoteForCausalLM, Dots3NoteBlock, Dots3NoteServed,
)
from .brumby import (  # noqa: F401
    BrumbyConfig, BrumbyForCausalLM, BrumbyBlock, BrumbyServed,
)
from .xing4 import (  # noqa: F401
    Xing4Config, Xing4ForCausalLM, Xing4Block, Xing4Served,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig, NemotronHForCausalLM, NemotronHBlock, NemotronHServed,
)
from .zaya1 import (  # noqa: F401
    Zaya1Config, Zaya1ForCausalLM, Zaya1Block, Zaya1Served,
)
