from .llama import (  # noqa: F401
    LlamaConfig, LlamaModel, LlamaForCausalLM, LlamaDecoderLayer, LlamaAttention,
    LlamaMLP, precompute_rope, apply_rope,
)
from .bert import BertConfig, BertModel, BertForMaskedLM  # noqa: F401
from .gpt import (  # noqa: F401
    GPTConfig, GPT2Model, GPT2LMHeadModel, gpt2_small, gpt2_medium,
)
from .unet import (  # noqa: F401
    UNetConfig, UNetModel, sd_unet, diffusion_loss, timestep_embedding,
)
from .kimi_linear import (  # noqa: F401
    KimiLinearConfig, KimiLinearForCausalLM)
from .deepseek_v2 import (  # noqa: F401
    DeepseekV2Config, DeepseekV2ForCausalLM)
from .ouro import OuroConfig, OuroForCausalLM  # noqa: F401
from .brumby import BrumbyConfig, BrumbyForCausalLM  # noqa: F401
from .solar_open2 import (  # noqa: F401
    SolarOpen2Config, SolarOpen2ForCausalLM)
from .dots3_note import (  # noqa: F401
    Dots3NoteConfig, Dots3NoteForCausalLM)
from .lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig, Lfm2MoeForCausalLM)
