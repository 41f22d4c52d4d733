"""On-chip throughput for the non-Llama BASELINE.json workload configs.

`bench.py` owns the Llama headline; this tool measures the other four
workload families the metric contract lists (BASELINE.json "configs"):

  resnet50    ResNet-50 train step, 224x224 synthetic images  -> img/s
  bert_base   BERT-base MLM+NSP pretrain step, seq 128        -> tok/s
  ernie_moe   ERNIE-style MoE causal-LM train step (dense-eq) -> tok/s
  sdxl_unet   SDXL-class UNet: denoise inference step at the
              base config (2.6B params, bf16) + a reduced-width
              train step that fits one v5e                    -> step ms

One point per process (a crash or OOM costs one child, never the
session; one process on the chip at a time):

    python bench_workloads.py <name>

prints one `WORKLOAD {json}` line.

MFU accounting: utilization = executed-FLOPs / (time x peak), with
executed FLOPs taken from XLA's cost analysis of the compiled step
(uniform across model families; falls back to an analytic estimate
when the backend reports none). Llama's bench.py number instead uses
the analytic 6*N*T "model FLOPs" convention; both are recorded.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from paddle_tpu.utils.flags import env_bool

PEAK = 197e12  # v5e bf16 peak FLOP/s
HBM_LIMIT = 15.2e9
# PT_WORKLOADS_TINY=1: shrink every config/shape so the whole file can
# be smoke-tested on CPU (tests/test_bench_workloads.py) before a chip
# session spends its window on it.
TINY = env_bool("PT_WORKLOADS_TINY")


def _compiled_flops(step, batch_t):
    """XLA cost-model FLOPs for one compiled step (or -1)."""
    try:
        compiled = step.lower(batch_t).compile()
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return float(cost.get("flops", -1.0)), compiled
    except Exception:
        return -1.0, None


def _precheck(compiled, limit=HBM_LIMIT):
    if compiled is None or limit is None:
        return
    ma = compiled.memory_analysis()
    est = (getattr(ma, "temp_size_in_bytes", 0)
           + getattr(ma, "argument_size_in_bytes", 0)
           + getattr(ma, "output_size_in_bytes", 0)
           - getattr(ma, "alias_size_in_bytes", 0))
    if est > limit:
        raise RuntimeError(
            f"AOT memory precheck: {est / 1e9:.2f} GB > "
            f"{limit / 1e9:.2f} GB; skipping execution")


class _NoScan:
    """Hides run_steps so _time_step's scan path (one extra XLA
    program) is skipped for TINY families with full_machinery=False."""

    def __init__(self, step):
        self._step = step

    def __call__(self, batch_t):
        return self._step(batch_t)


def _time_step(step, batch_t, steps, warmup):
    import paddle_tpu  # noqa: F401  (ensures backend is up)
    for _ in range(warmup):
        out = step(batch_t)
    _sync(out)
    if hasattr(step, "run_steps"):
        # one lax.scan dispatch for the whole timed window (no per-step
        # host round-trip; see bench.py)
        try:
            out = step.run_steps(batch_t, steps)
            _sync(out)
            t0 = time.perf_counter()
            out = step.run_steps(batch_t, steps)
            final = _sync(out)
            return (time.perf_counter() - t0) / steps, final
        except Exception:
            pass
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step(batch_t)
    final = _sync(out)
    return (time.perf_counter() - t0) / steps, final


def _sync(out):
    loss = out[0] if isinstance(out, (tuple, list)) else out
    try:
        return float(loss.item())
    except Exception:
        import jax
        jax.block_until_ready(getattr(loss, "_value", loss))
        return -1.0


def _train_common(model, loss_fn, batch_t, steps, warmup, analytic_flops,
                  full_machinery=True):
    """Shared train-step measurement: AOT flops + precheck, then timing.

    ``full_machinery=False`` (TINY smoke only) skips the AOT
    cost-analysis compile and the run_steps scan compile — each TINY
    family otherwise pays 3 XLA programs for machinery that one family
    (ernie_moe keeps full_machinery=True) already covers; on chip every
    family always runs the full path."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep

    opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                          parameters=model.parameters(),
                          multi_precision=False)
    step = TrainStep(model, loss_fn, opt)
    if full_machinery or not TINY:
        xla_flops, compiled = _compiled_flops(step, batch_t)
        _precheck(compiled)
    else:
        xla_flops, compiled = -1.0, None
        step = _NoScan(step)
    step_s, final = _time_step(step, batch_t, steps, warmup)
    flops = xla_flops if xla_flops > 0 else analytic_flops
    return {
        "step_ms": round(step_s * 1000, 2),
        "final_loss": round(final, 4),
        "model_params": int(model.num_params()) if hasattr(
            model, "num_params") else int(sum(
                int(np.prod(p.shape)) for p in model.parameters())),
        "xla_step_flops": xla_flops,
        "utilization_vs_peak": round(flops / step_s / PEAK, 4)
        if flops > 0 else None,
    }


def resnet50():
    import paddle_tpu as paddle
    from paddle_tpu import amp, nn
    from paddle_tpu.vision.models import resnet50 as build

    paddle.seed(0)
    batch, hw, ncls = (2, 32, 10) if TINY else (64, 224, 1000)
    if TINY:
        # tool-machinery smoke only: resnet18 walks the identical code
        # path (amp decorate, TrainStep, AOT precheck, timing) at a
        # third of the CPU compile cost of the 50-layer build
        from paddle_tpu.vision.models import resnet18 as build
    model = build(num_classes=ncls)
    amp.decorate(model, level="O2", dtype="bfloat16")
    ce = nn.CrossEntropyLoss()

    def loss_fn(m, b):
        img, label = b
        with amp.auto_cast(dtype="bfloat16", level="O2"):
            logits = m(img)
        return ce(logits.astype("float32"), label)

    img = paddle.to_tensor(
        np.random.randn(batch, 3, hw, hw).astype(np.float32)
        ).astype("bfloat16")  # O2: conv weights are bf16
    label = paddle.to_tensor(
        np.random.randint(0, ncls, (batch,)).astype(np.int64))
    r = _train_common(model, loss_fn, (img, label),
                      steps=2 if TINY else 10, warmup=1 if TINY else 3,
                      # analytic: ~4.1 GFLOP fwd per 224x224 img, x3 bwd
                      analytic_flops=batch * 4.1e9 * 3,
                      full_machinery=not TINY)
    return {"workload": ("resnet18_train_tiny_smoke" if TINY
                         else "resnet50_train"), "images_per_sec":
            round(batch / (r["step_ms"] / 1000), 1), "batch": batch,
            "image_size": hw, **r}


def bert_base():
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.models.bert import BertForPretraining, bert_base_config

    paddle.seed(0)
    batch, seq = (2, 32) if TINY else (64, 128)  # phase-1 pretrain shape
    if TINY:
        from paddle_tpu.models.bert import bert_tiny_config
        cfg = bert_tiny_config()
    else:
        cfg = bert_base_config()
    model = BertForPretraining(cfg)
    amp.decorate(model, level="O2", dtype="bfloat16")

    ids = np.random.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int32)
    nsp = np.random.randint(0, 2, (batch,)).astype(np.int64)

    def loss_fn(m, b):
        i, l, n = b
        # LayerNorms stay fp32 under decorate; the cast scope keeps the
        # matmuls after them in bf16 instead of silently promoting
        with amp.auto_cast(dtype="bfloat16", level="O2"):
            out = m(i, masked_lm_labels=l, next_sentence_labels=n)
        return out[0] if isinstance(out, (tuple, list)) else out

    batch_t = (paddle.to_tensor(ids), paddle.to_tensor(labels),
               paddle.to_tensor(nsp))
    params = sum(int(np.prod(p.shape)) for p in model.parameters())
    r = _train_common(model, loss_fn, batch_t,
                      steps=2 if TINY else 10, warmup=1 if TINY else 3,
                      analytic_flops=6 * params * batch * seq,
                      full_machinery=not TINY)
    tok_s = batch * seq / (r["step_ms"] / 1000)
    return {"workload": "bert_base_pretrain", "tokens_per_sec":
            round(tok_s, 1), "batch": batch, "seq": seq, **r}


def ernie_moe():
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.models.ernie_moe import (ErnieMoEForCausalLM,
                                             ernie_moe_base_config)

    paddle.seed(0)
    batch, seq = (2, 32) if TINY else (16, 1024)
    if TINY:
        from paddle_tpu.models.ernie_moe import ernie_moe_tiny_config
        cfg = ernie_moe_tiny_config(expert_parallel=False)
    else:
        cfg = ernie_moe_base_config(expert_parallel=False)
    model = ErnieMoEForCausalLM(cfg)
    amp.decorate(model, level="O2", dtype="bfloat16")

    ids = np.random.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int32)

    def loss_fn(m, b):
        i, l = b
        with amp.auto_cast(dtype="bfloat16", level="O2"):
            out = m(i, labels=l)
        return out[0] if isinstance(out, (tuple, list)) else out

    batch_t = (paddle.to_tensor(ids), paddle.to_tensor(labels))
    # analytic fallback must count ACTIVE params: only top_k of
    # num_experts expert MLPs run per token
    expert_p = sum(int(np.prod(p.shape)) for n, p in
                   model.named_parameters() if ".experts." in n)
    total_p = sum(int(np.prod(p.shape)) for p in model.parameters())
    active_p = total_p - expert_p * (1 - cfg.top_k / cfg.num_experts)
    r = _train_common(model, loss_fn, batch_t,
                      steps=2 if TINY else 8, warmup=1 if TINY else 2,
                      analytic_flops=6 * active_p * batch * seq)
    tok_s = batch * seq / (r["step_ms"] / 1000)
    return {"workload": "ernie_moe_train", "tokens_per_sec":
            round(tok_s, 1), "batch": batch, "seq": seq,
            "num_experts": cfg.num_experts, "top_k": cfg.top_k,
            "active_params": int(active_p), **r}


def sdxl_unet():
    """Two numbers: (a) denoise inference step at the full SDXL base
    config (the serving workload; params-only bf16 fits v5e), (b) a
    train step at a reduced-width config that fits with Adam states."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models.diffusion import (UNet2DConditionModel,
                                             sdxl_base_config)
    import paddle_tpu.jit as pjit

    paddle.seed(0)
    out = {"workload": "sdxl_unet"}

    # (a) inference denoise step, full base config, bf16 params
    batch = 1 if TINY else 4
    latent = 8 if TINY else 128
    paddle.set_default_dtype("bfloat16")
    try:
        if TINY:
            from paddle_tpu.models.diffusion import sdxl_tiny_config
            cfg = sdxl_tiny_config(dtype="bfloat16")
        else:
            cfg = sdxl_base_config(sample_size=128, dtype="bfloat16")
        unet = UNet2DConditionModel(cfg)
    finally:
        paddle.set_default_dtype("float32")
    lat = paddle.to_tensor(np.random.randn(
        batch, 4, latent, latent).astype(np.float32)).astype("bfloat16")
    t = paddle.to_tensor(np.full((batch,), 500, np.int32))
    ctx = paddle.to_tensor(np.random.randn(
        batch, 77, cfg.cross_attention_dim).astype(np.float32)
        ).astype("bfloat16")
    added = None
    if cfg.addition_embed_dim:
        added = paddle.to_tensor(np.random.randn(
            batch, cfg.addition_embed_dim).astype(np.float32)
            ).astype("bfloat16")

    @pjit.to_static
    def denoise(lat, t, ctx, added):
        return unet(lat, t, ctx, added_cond=added)

    iters = 2 if TINY else 8
    for _ in range(1 if TINY else 3):
        o = denoise(lat, t, ctx, added)
    _sync(o)
    t0 = time.perf_counter()
    for _ in range(iters):
        o = denoise(lat, t, ctx, added)
    _sync(o)
    dt = (time.perf_counter() - t0) / iters
    out["infer_params"] = sum(
        int(np.prod(p.shape)) for p in unet.parameters())
    out["infer_batch"] = batch
    out["infer_latent"] = latent
    out["infer_step_ms"] = round(dt * 1000, 2)
    out["infer_images_per_sec_at_30steps"] = round(batch / (dt * 30), 2)
    del unet, denoise, lat, ctx, added

    # (b) train step, reduced width (fits params+moments+activations)
    paddle.seed(0)
    tb, tlat = (1, 8) if TINY else (8, 64)
    paddle.set_default_dtype("bfloat16")
    try:
        if TINY:
            from paddle_tpu.models.diffusion import sdxl_tiny_config
            cfg2 = sdxl_tiny_config(dtype="bfloat16")
        else:
            cfg2 = sdxl_base_config(
                sample_size=64, block_out_channels=(192, 384, 768),
                transformer_layers=(0, 2, 6),
                num_attention_heads=(3, 6, 12),
                cross_attention_dim=1024, addition_embed_dim=0,
                dtype="bfloat16")
        unet2 = UNet2DConditionModel(cfg2)
    finally:
        paddle.set_default_dtype("float32")

    mse = nn.MSELoss()

    def loss_fn(m, b):
        lat, t, ctx, noise = b
        return mse(m(lat, t, ctx), noise)

    lat = paddle.to_tensor(np.random.randn(
        tb, 4, tlat, tlat).astype(np.float32)).astype("bfloat16")
    t2 = paddle.to_tensor(np.full((tb,), 500, np.int32))
    ctx2 = paddle.to_tensor(np.random.randn(
        tb, 77, cfg2.cross_attention_dim).astype(np.float32)
        ).astype("bfloat16")
    noise = paddle.to_tensor(np.random.randn(
        tb, 4, tlat, tlat).astype(np.float32)).astype("bfloat16")
    batch_t = (lat, t2, ctx2, noise)
    r = _train_common(unet2, loss_fn, batch_t,
                      steps=2 if TINY else 8, warmup=1 if TINY else 2,
                      analytic_flops=-1, full_machinery=not TINY)
    out.update({"train_" + k: v for k, v in r.items()})
    out["train_batch"] = tb
    out["train_latent"] = tlat
    return out


def llama_serve():
    """Continuous-batching serving throughput (paddle_tpu/serving/):
    mixed-length staggered request stream through the slot-pool engine
    vs static-batch generate() — the serving analogue of the training
    workloads' tok/s. TINY runs the same machinery on llama-tiny."""
    from bench import _bench_continuous_decode
    from paddle_tpu.models.llama import LlamaConfig, llama_tiny_config

    if TINY:
        cfg = llama_tiny_config(tensor_parallel=False)
        r = _bench_continuous_decode(cfg, num_slots=2, decode_block=4,
                                     long_new=12, short_new=4)
    else:
        # the 0.27B bench config: serving throughput at a real size
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=1024,
            tensor_parallel=False)
        r = _bench_continuous_decode(cfg, num_slots=8, decode_block=8)
    return {"workload": ("llama_serve_tiny_smoke" if TINY
                         else "llama_serve_continuous"),
            "tokens_per_sec": r["decode_tokens_per_sec"], **r}


WORKLOADS = {"resnet50": resnet50, "bert_base": bert_base,
             "ernie_moe": ernie_moe, "sdxl_unet": sdxl_unet,
             "llama_serve": llama_serve}


if __name__ == "__main__":
    # several names in one invocation share the interpreter/jax startup
    # (the CPU smoke tests run all four in one process; on the chip
    # keep one point per process)
    names = sys.argv[1:]
    from _bench_common import configure_jax
    configure_jax()
    for name in names:
        try:
            r = WORKLOADS[name]()
            print("WORKLOAD " + json.dumps(r), flush=True)
        except Exception as e:
            print("WORKLOAD " + json.dumps(
                {"workload": name,
                 "error": f"{type(e).__name__}: {e}"[:300]}), flush=True)
