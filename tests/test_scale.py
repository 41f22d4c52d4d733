"""Abstract (weight-free) AOT scale-check machinery (VERDICT r1 #4:
13B readiness without hardware): utils/scale.py validated at tiny size
on 8 devices."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.distributed.mesh import set_current_mesh
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.utils.scale import (abstract_init, attach_shardings,
                                    abstract_state_specs)


@pytest.fixture(autouse=True)
def _clear_mesh():
    yield
    set_current_mesh(None)


def _compile(cfg, mesh, dtype, batch=4, seq=32):
    with abstract_init(dtype=dtype):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
    attach_shardings(model, mesh)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=False)

    def loss_fn(m, b):
        ids, labels = b
        loss, _ = m(ids, labels)
        return loss
    step = TrainStep(model, loss_fn, opt)
    step._build()
    pvals = {n: t._value for n, t in step._ptensors.items()}
    opt._slots = abstract_state_specs(opt.functional_state(),
                                      pvals)["slots"]
    for _, b in model.named_buffers():
        b._update_value(jax.device_put(b._value, NamedSharding(mesh, P())))
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    return model, step.lower((ids, ids)).compile()


class TestAbstractScale:
    def test_params_never_materialized(self):
        with abstract_init(dtype="bfloat16"):
            paddle.seed(0)
            model = LlamaForCausalLM(llama_tiny_config(
                tensor_parallel=True))
        for _, p in model.named_parameters():
            assert isinstance(p._value, jax.ShapeDtypeStruct)
            assert p._value.dtype == jnp.bfloat16

    def test_tp_compiles_with_per_device_memory(self):
        mesh = Mesh(np.array(jax.devices()), ("mp",))
        set_current_mesh(mesh)
        cfg = llama_tiny_config(tensor_parallel=True)
        model, compiled = _compile(cfg, mesh, "bfloat16")
        ma = compiled.memory_analysis()
        # per-device argument bytes ≈ sharded params + slots: far below
        # the replicated total (2 moments + params + grads in bf16)
        n_params = sum(int(np.prod(p._value.shape))
                       for _, p in model.named_parameters())
        replicated_bytes = n_params * 2 * 3
        assert 0 < ma.argument_size_in_bytes < replicated_bytes

    def test_tp_pp_compiles_f32(self):
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pp", "mp"))
        set_current_mesh(mesh)
        cfg = llama_tiny_config(tensor_parallel=True,
                                pipeline_parallel=True,
                                pp_num_microbatches=2, recompute=True)
        model, compiled = _compile(cfg, mesh, "float32")
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes > 0
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        assert float(ca.get("flops", 0)) > 0
