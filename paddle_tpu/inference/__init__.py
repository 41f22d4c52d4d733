"""Inference deployment (``paddle.inference`` parity).

Reference parity: paddle/fluid/inference/ — AnalysisConfig +
AnalysisPredictor + zero-copy tensors (paddle/fluid/inference/api/
analysis_predictor.cc, paddle_inference_api.h — verify).

TPU-native design: "analysis passes + saved program" becomes AOT
compilation — the model is traced once, exported as serialized
StableHLO (jax.export) with weights stored alongside, and the
predictor executes the compiled artifact. XLA does the reference's
fusion/quant passes at compile time; TensorRT-subgraph offload has no
TPU analog (XLA *is* the whole-graph compiler)."""
from __future__ import annotations

import os
import pickle
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Config", "Predictor", "create_predictor", "export_model",
           "convert_to_predictor", "PrecisionType", "export_decoder",
           "GenerationPredictor"]


class PrecisionType:
    Float32 = "float32"
    Bfloat16 = "bfloat16"
    Half = "float16"
    Int8 = "int8"


class Config:
    """AnalysisConfig analog. IR/memory switches are accepted for API
    parity; XLA already performs those optimizations."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        self.model_path = prog_file
        self.params_path = params_file
        self._precision = PrecisionType.Float32
        self._device = None
        self._glog_info = True
        self._memory_optim = True
        self._ir_optim = True

    def set_model(self, prog_file, params_file=None):
        self.model_path = prog_file
        self.params_path = params_file

    def set_prog_file(self, path):
        self.model_path = path

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._device = f"tpu:{device_id}"  # gpu calls map to the TPU chip

    def disable_gpu(self):
        self._device = "cpu"

    def enable_memory_optim(self, flag=True):
        self._memory_optim = flag

    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def set_cpu_math_library_num_threads(self, n):
        pass

    def disable_glog_info(self):
        self._glog_info = False

    def precision(self):
        return self._precision


class _IOHandle:
    """Zero-copy-style tensor handle (paddle_infer.Tensor analog)."""

    def __init__(self, name: str, spec: jax.ShapeDtypeStruct):
        self.name = name
        self._spec = spec
        self._value = None

    def shape(self):
        return list(self._spec.shape)

    def copy_from_cpu(self, arr: np.ndarray):
        self._value = jnp.asarray(arr)

    def share_external_data(self, arr):
        self._value = arr if isinstance(arr, jax.Array) else \
            jnp.asarray(arr)

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._value)


def export_model(layer, input_spec: Sequence, path: str):
    """Trace + AOT-export a Layer: serialized StableHLO with weights.
    ``input_spec``: static.InputSpec / Tensor / ndarray examples."""
    from ..nn import Layer
    from ..static import InputSpec
    from ..tensor import Tensor
    from .. import framework

    _sym_count = [0]
    _scope = [None]  # ONE scope for the whole export: symbolic dims from
    #                  different scopes cannot be mixed in one program

    def _shape(dims):
        """-1/None dims (InputSpec dynamic axes) become jax.export
        symbolic dimensions, so one exported program serves any size on
        that axis — the reference's dynamic-shape ProgramDesc export."""
        out = []
        for d in dims:
            if d is None or (isinstance(d, int) and d < 0):
                if _scope[0] is None:
                    _scope[0] = jax.export.SymbolicScope()
                _sym_count[0] += 1
                out.append(jax.export.symbolic_shape(
                    f"_dyn{_sym_count[0]}", scope=_scope[0])[0])
            else:
                out.append(int(d))
        return tuple(out)

    def to_sds(s):
        if isinstance(s, InputSpec):
            return jax.ShapeDtypeStruct(_shape(s.shape),
                                        framework.convert_dtype(s.dtype))
        if isinstance(s, Tensor):
            return jax.ShapeDtypeStruct(tuple(s.shape),
                                        s._value.dtype)
        arr = np.asarray(s)
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

    specs = [to_sds(s) for s in input_spec]
    ptensors = dict(layer.named_parameters())
    btensors = dict(layer.named_buffers())
    pvals = {k: t._value for k, t in ptensors.items()}
    bvals = {k: t._value for k, t in btensors.items()}

    def fn(pv, bv, *inputs):
        saved = [(t, t._value) for t in
                 list(ptensors.values()) + list(btensors.values())]
        try:
            for k, v in pv.items():
                ptensors[k]._value = v
            for k, v in bv.items():
                btensors[k]._value = v
            was_training = layer.training
            layer.eval()
            try:
                with framework.functional_mode(), framework.rng_context(
                        jax.random.PRNGKey(0)):
                    out = layer(*[Tensor(x) for x in inputs])
            finally:
                if was_training:
                    layer.train()
            return jax.tree_util.tree_map(
                lambda o: o._value if isinstance(o, Tensor) else o, out,
                is_leaf=lambda o: isinstance(o, Tensor))
        finally:
            for t, v in saved:
                t._value = v

    pspecs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in pvals.items()}
    bspecs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in bvals.items()}
    exported = jax.export.export(jax.jit(fn))(pspecs, bspecs, *specs)
    blob = {
        "stablehlo": exported.serialize(),
        "params": {k: np.asarray(v) for k, v in pvals.items()},
        "buffers": {k: np.asarray(v) for k, v in bvals.items()},
        "input_specs": [(tuple(d if isinstance(d, int) else -1
                               for d in s.shape), str(s.dtype))
                        for s in specs],
        "input_names": [f"x{i}" for i in range(len(specs))],
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump(blob, f, protocol=pickle.HIGHEST_PROTOCOL)
    return path + ".pdmodel"


class Predictor:
    """AnalysisPredictor analog over a serialized StableHLO artifact."""

    def __init__(self, config: Config):
        self.config = config
        path = config.model_path
        if path is None:
            raise ValueError("Config.set_model(path) before "
                             "create_predictor")
        if not path.endswith(".pdmodel"):
            path = path + ".pdmodel"
        with open(path, "rb") as f:
            blob = pickle.load(f)
        self._exported = jax.export.deserialize(blob["stablehlo"])
        self._params = {k: jnp.asarray(v)
                        for k, v in blob["params"].items()}
        self._buffers = {k: jnp.asarray(v)
                         for k, v in blob["buffers"].items()}
        self._input_names: List[str] = blob["input_names"]
        self._input_specs = [
            jax.ShapeDtypeStruct(shape, np.dtype(dtype))
            for shape, dtype in blob["input_specs"]]
        self._inputs: Dict[str, _IOHandle] = {
            n: _IOHandle(n, s)
            for n, s in zip(self._input_names, self._input_specs)}
        self._outputs: List = []

    def get_input_names(self):
        return list(self._input_names)

    def get_input_handle(self, name) -> _IOHandle:
        return self._inputs[name]

    def run_on_device(self, args: Sequence):
        """Zero-copy path: device (or jnp-convertible) inputs in, device
        arrays out — no host round trip (used by jit.TranslatedLayer)."""
        out = self._exported.call(self._params, self._buffers,
                                  *[jnp.asarray(a) for a in args])
        self._outputs = list(out) if isinstance(out, (tuple, list)) \
            else [out]
        return self._outputs

    def run(self, inputs: Optional[Sequence[np.ndarray]] = None):
        if inputs is not None:
            for n, arr in zip(self._input_names, inputs):
                self._inputs[n].copy_from_cpu(np.asarray(arr))
        args = [self._inputs[n]._value for n in self._input_names]
        if any(a is None for a in args):
            missing = [n for n in self._input_names
                       if self._inputs[n]._value is None]
            raise RuntimeError(f"inputs not set: {missing}")
        self.run_on_device(args)
        if inputs is not None:
            return [np.asarray(o) for o in self._outputs]
        return None

    def get_output_names(self):
        return [f"out{i}" for i in range(len(self._outputs))]

    def get_output_handle(self, name) -> _IOHandle:
        i = int(name.replace("out", "") or 0)
        h = _IOHandle(name, jax.ShapeDtypeStruct(
            self._outputs[i].shape, self._outputs[i].dtype))
        h._value = self._outputs[i]
        return h


def export_decoder(model, path: str, batch: int, prompt_len: int,
                   max_len: int, temperature: float = 0.0,
                   top_k: int = 0, top_p: float = 1.0,
                   engine_slots: Optional[int] = None,
                   engine_decode_block: int = 8,
                   engine_block_size: int = 16,
                   engine_num_blocks: Optional[int] = None,
                   engine_prefill_chunk: Optional[int] = None):
    """AOT-export the autoregressive serving path of a causal LM: TWO
    StableHLO programs — prefill (prompt → first token + KV cache) and
    decode step (token, cache, pos → next token, cache) — plus weights
    (reference: AnalysisPredictor serving autoregressive models,
    SURVEY §3.5; the decode loop then runs without Python tracing).

    The model must implement ``init_kv_cache`` and a cached ``forward``
    (see models/generation.GenerationMixin). The SAME pure step function
    as GenerationMixin.generate is exported twice — once specialized to
    the prompt block at pos=0 (prefill, cache zero-initialized inside),
    once to a single token — so in-process and served decoding share one
    implementation.

    ``engine_slots``: additionally export the continuous-batching
    engine's two programs — the decode block over the block arena
    (in-state block tables) and the ONE chunked-prefill chunk program —
    so ``GenerationPredictor.serve()`` runs the SAME serving engine from
    the artifact alone (``serving.paging.PagedArtifactStepBackend`` is
    the loader; see ``paddle_tpu.serving``). ``engine_block_size`` /
    ``engine_num_blocks`` / ``engine_prefill_chunk`` mirror the
    engine's ``block_size`` / ``num_blocks`` / ``prefill_chunk``
    (defaults match: every slot's whole table + trash block, chunk =
    ``serving.paging.default_prefill_chunk``). The artifact records the
    program arities (``block_outputs``/``chunk_outputs``) so a serving
    host can tell what it loaded. The int8 KV arena is not exported
    (fp32 arena only)."""
    from ..models.generation import build_decode_step
    from ..tensor import Tensor

    if engine_slots is not None:
        from ..serving.paging import refuse_looped_cache
        refuse_looped_cache(model, "the exported paged artifact")
    sample_kwargs = dict(temperature=temperature, top_k=top_k,
                         top_p=top_p)
    pvals = [p._value for _, p in model.named_parameters()]
    bvals = [b._value for _, b in model.named_buffers()]
    cache0 = model.init_kv_cache(batch, max_len)
    flat0, tree = jax.tree.flatten(
        cache0, is_leaf=lambda x: isinstance(x, Tensor))
    cache_specs = tuple(jax.ShapeDtypeStruct(c._value.shape,
                                             c._value.dtype)
                        for c in flat0)
    tree_holder = {"tree": tree}
    step = build_decode_step(model, sample_kwargs, tree_holder)

    def prefill(pv, bv, ids, key):
        zero_cache = tuple(jnp.zeros(s.shape, s.dtype)
                           for s in cache_specs)
        return step(pv, bv, ids, zero_cache,
                    jnp.asarray(0, jnp.int32), key)

    pspecs = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in pvals]
    bspecs = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in bvals]
    ids_spec = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
    tok_spec = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    exp_prefill = jax.export.export(jax.jit(prefill))(
        pspecs, bspecs, ids_spec, key_spec)
    exp_step = jax.export.export(jax.jit(step))(
        pspecs, bspecs, tok_spec, cache_specs, pos_spec, key_spec)
    blob = {
        "prefill": exp_prefill.serialize(),
        "step": exp_step.serialize(),
        "params": [np.asarray(v) for v in pvals],
        "buffers": [np.asarray(v) for v in bvals],
        "gen_config": {"batch": batch, "prompt_len": prompt_len,
                       "max_len": max_len, **sample_kwargs},
    }
    if engine_slots is not None:
        from ..serving.engine import (build_paged_chunk_fn,
                                      build_slot_block_fn,
                                      init_slot_state)
        if max_len % engine_block_size != 0:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"engine_block_size={engine_block_size}")
        max_blocks = max_len // engine_block_size
        if engine_num_blocks is None:
            engine_num_blocks = 1 + engine_slots * max_blocks
        if engine_prefill_chunk is None:
            from ..serving.paging import default_prefill_chunk
            engine_prefill_chunk = default_prefill_chunk(
                engine_block_size, max_len)
        pool0 = model.init_paged_kv_cache(engine_num_blocks,
                                          engine_block_size)
        pflat, ptree = jax.tree.flatten(
            pool0, is_leaf=lambda x: isinstance(x, Tensor))
        eng_holder = {"tree": ptree}
        eng_pure = build_decode_step(model, None, eng_holder)
        pool_specs = tuple(jax.ShapeDtypeStruct(c._value.shape,
                                                c._value.dtype)
                           for c in pflat)
        state_specs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            init_slot_state(engine_slots))
        state_specs["table"] = jax.ShapeDtypeStruct(
            (engine_slots, max_blocks), jnp.int32)
        block_fn = build_slot_block_fn(eng_pure, engine_decode_block)
        exp_block = jax.export.export(jax.jit(block_fn))(
            pspecs, bspecs, pool_specs, state_specs)
        chunk_fn = build_paged_chunk_fn(eng_pure, engine_prefill_chunk)
        exp_chunk = jax.export.export(jax.jit(chunk_fn))(
            pspecs, bspecs,
            jax.ShapeDtypeStruct((1, engine_prefill_chunk), jnp.int32),
            pool_specs,
            jax.ShapeDtypeStruct((1, max_blocks), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32))
        blob["engine"] = {
            "block": exp_block.serialize(),
            "chunk": exp_chunk.serialize(),
            "pool_specs": [(tuple(s.shape), str(np.dtype(s.dtype)))
                           for s in pool_specs],
            # arities recorded: the block emits (cache, state, toks,
            # lives, oks), the chunk program (tok0, cache) — a serving
            # host can tell what the artifact carries without
            # deserializing anything
            "config": {"paged": True, "num_slots": engine_slots,
                       "max_len": max_len,
                       "decode_block": engine_decode_block,
                       "block_size": engine_block_size,
                       "num_blocks": engine_num_blocks,
                       "prefill_chunk": engine_prefill_chunk,
                       "kv_int8": False,
                       "block_outputs": 5, "chunk_outputs": 2},
        }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    out = path + ".pdgen"
    with open(out, "wb") as f:
        pickle.dump(blob, f, protocol=pickle.HIGHEST_PROTOCOL)
    return out


class GenerationPredictor:
    """Serving-side decode loop over the AOT artifact of
    :func:`export_decoder` — no model code or tracing needed."""

    def __init__(self, path: str):
        if not path.endswith(".pdgen"):
            path = path + ".pdgen"
        with open(path, "rb") as f:
            blob = pickle.load(f)
        self._prefill = jax.export.deserialize(blob["prefill"])
        self._step = jax.export.deserialize(blob["step"])
        self._params = [jnp.asarray(v) for v in blob["params"]]
        self._buffers = [jnp.asarray(v) for v in blob["buffers"]]
        self.gen_config = blob["gen_config"]
        self._engine_blob = blob if "engine" in blob else None
        self._server = None

    def generate(self, input_ids: np.ndarray, max_new_tokens: int = 20,
                 seed: int = 0) -> np.ndarray:
        cfg = self.gen_config
        ids = jnp.asarray(np.asarray(input_ids), jnp.int32)
        b, s = ids.shape
        if (b, s) != (cfg["batch"], cfg["prompt_len"]):
            raise ValueError(
                f"input shape {(b, s)} != exported "
                f"({cfg['batch']}, {cfg['prompt_len']})")
        if max_new_tokens <= 0:
            return np.asarray(ids)
        capacity = cfg["max_len"] - s
        if max_new_tokens > capacity:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} exceeds the exported "
                f"cache capacity ({capacity} = max_len {cfg['max_len']} "
                f"- prompt {s}); re-export with a larger max_len")
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        tok, cache = self._prefill.call(self._params, self._buffers,
                                        ids, sub)
        toks = [tok]
        for i in range(1, max_new_tokens):
            key, sub = jax.random.split(key)
            pos = jnp.asarray(s + i - 1, jnp.int32)
            tok, cache = self._step.call(self._params, self._buffers,
                                         tok[:, None], tuple(cache),
                                         pos, sub)
            toks.append(tok)
        gen = jnp.stack(toks, axis=1)
        return np.asarray(jnp.concatenate([ids, gen], axis=1))

    def serve(self, requests, run: bool = True):
        """Continuous-batching serving from the artifact alone: builds
        the SAME ``serving.Server`` loop over the exported engine
        programs (requires ``export_decoder(..., engine_slots=N)``).
        ``requests``: iterable of dicts with keys matching
        :meth:`serving.Server.submit` (``prompt`` required).
        Returns the Server (``run=False``) or its results dict."""
        if self._engine_blob is None:
            raise ValueError(
                "this artifact has no engine programs; re-export with "
                "export_decoder(..., engine_slots=N)")
        from ..serving import ContinuousBatchingEngine, Server
        from ..serving.paging import PagedArtifactStepBackend
        if self._server is None:
            self._server = Server(ContinuousBatchingEngine(
                backend=PagedArtifactStepBackend(self._engine_blob)))
        server = self._server
        for req in requests:
            server.submit(**dict(req))
        if not run:
            return server
        return server.run_until_idle()


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def convert_to_predictor(layer, input_spec, path) -> Predictor:
    """export_model + create_predictor in one step."""
    model_path = export_model(layer, input_spec, path)
    cfg = Config(model_path)
    return Predictor(cfg)
