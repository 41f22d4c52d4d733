"""In-repo model zoo (the reference's model families live in ecosystem
repos — PaddleNLP/ppdiffusers; SURVEY §1 requires in-repo equivalents).
Families: llama (flagship), bert, gpt, t5 (encoder-decoder), moe
(ERNIE-style toy), deepseek_v3 (latent attention + dropless experts),
mimo_v2 (sliding-window layers with a sink mixed with full layers),
ouro (one stack of layers run several times, a cache for every pass),
dots3_note (latent attention at two geometries, a learned indexer that
picks the full layers' tokens, windowed latent layers, a headwise gate),
resnet (vision re-export), diffusion (SDXL-style UNet)."""
from . import llama      # noqa: F401
from . import bert       # noqa: F401
from . import gpt        # noqa: F401
from . import ernie_moe  # noqa: F401
from . import diffusion  # noqa: F401
from . import t5         # noqa: F401
from . import deepseek_v3  # noqa: F401
from .deepseek_v3 import (DeepseekV3Config,           # noqa: F401
                          DeepseekV3ForCausalLM)
from . import mimo_v2    # noqa: F401
from .mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM  # noqa: F401
from . import ouro       # noqa: F401
from .ouro import OuroConfig, OuroForCausalLM         # noqa: F401
from . import dots3_note  # noqa: F401
from .dots3_note import (Dots3NoteConfig,             # noqa: F401
                         Dots3NoteForCausalLM)

from ..vision.models import resnet50, resnet18, ResNet  # noqa: F401
