"""Text-to-image sampling with the in-repo diffusion stack: a T5 encoder
conditions the UNet (CLIP's role in SD/SDXL), classifier-free guidance
runs the whole denoising loop as ONE compiled lax.scan program, and the
AutoencoderKL decodes latents to pixels.

CPU smoke (tiny config, ~30s):
    python examples/text_to_image.py
On TPU the same code runs the sdxl_base_config; attention dispatches to
the Pallas flash kernels.
"""
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

# PT_EXAMPLE_TPU=1 runs on the chip; default pins CPU BEFORE any backend
# init (a process that touches the TPU holds it until it exits)
if os.environ.get("PT_EXAMPLE_TPU") != "1":
    jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.diffusion import (AutoencoderKL, DDIMScheduler,
                                         StableDiffusionPipeline,
                                         UNet2DConditionModel,
                                         sdxl_tiny_config)
from paddle_tpu.models.t5 import T5Model, t5_tiny_config


def main():
    paddle.seed(0)
    cfg = sdxl_tiny_config(sample_size=8)

    # text encoder: a tiny T5 encoder stack at the UNet context dim
    tcfg = t5_tiny_config(vocab_size=256, d_model=cfg.cross_attention_dim,
                          d_ff=64, num_layers=2, num_heads=2,
                          d_kv=cfg.cross_attention_dim // 2)
    t5 = T5Model(tcfg)

    def encode(text: str):
        ids = paddle.to_tensor(
            np.frombuffer(text.encode()[:16].ljust(16, b' '), np.uint8)
            .astype(np.int32)[None, :] % tcfg.vocab_size)
        return t5.encode(ids)

    prompt = encode("a photo of a tpu pod")
    negative = encode("")

    pipe = StableDiffusionPipeline(
        UNet2DConditionModel(cfg),
        AutoencoderKL(in_channels=3, latent_channels=cfg.in_channels,
                      block_out_channels=(8, 16)),
        DDIMScheduler())
    img = pipe(prompt, negative, steps=4, guidance_scale=5.0, seed=42)
    arr = np.asarray(img._value)
    print(f"image: shape={tuple(arr.shape)} "
          f"range=[{arr.min():.3f}, {arr.max():.3f}] finite={np.isfinite(arr).all()}")


if __name__ == "__main__":
    main()
