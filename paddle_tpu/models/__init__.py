"""In-repo model zoo (the reference's model families live in ecosystem
repos — PaddleNLP/ppdiffusers; SURVEY §1 requires in-repo equivalents).
Families: llama (flagship), bert, gpt, t5 (encoder-decoder), moe
(ERNIE-style toy), deepseek_v3 (latent attention + dropless experts),
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

from ..vision.models import resnet50, resnet18, ResNet  # noqa: F401
