"""Bandwidth-true quantized serving (serving/quant.py +
ops/pallas/paged_attention.py int8 in-read dequant):

- in-kernel/in-read int8-KV decode parity pinned against the
  dequant-then-dense reference (interpret-mode kernel AND the CPU
  per-block scan fallback), plus greedy engine streams token-identical
  to the oracle route;
- a recursive jaxpr walk asserting the quantized decode program holds
  NO dense fp32 KV transient (neither the arena shape nor the gathered
  per-slot dense shape);
- weight-only int8/int4 serving: engine streams BIT-IDENTICAL to
  generate() on a host-dequantized twin model (the in-graph dequant is
  exact), composing with kv_int8/spec, with the
  runtime-queryable error bounds and registry bytes accounting;
- the five compositions the engine factory builds (fp32, int8 arena,
  int8/int4 weights, speculative) over ONE ragged stream, each
  against its reference with one decode compile;
- the routing matrix: explicit backends never rerouted by
  PT_SERVING_QUANT_WEIGHTS, quant= alongside an explicit backend /
  bogus configs / psum+quant refused loudly.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paged_walk_cases import WALK_CASES, chunk_rows, walk_inputs

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.nn.quant import dequantize_array, quantize_array
from paddle_tpu.serving import (ContinuousBatchingEngine, QuantConfig,
                                Scheduler, Server, SpecConfig,
                                SpecPagedEngine)
from paddle_tpu.serving.quant import resolve_quant_config

_QUANT_PATTERNS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                   "up_proj", "down_proj", "lm_head")


def _dequant_twin(model, cfg, bits, group_size=-1):
    """``model`` with every served weight sent through the host's
    quantize -> dequantize: THE oracle, since the engine's in-graph
    dequant must make quantized serving bit-identical to generate() on
    it."""
    twin = LlamaForCausalLM(cfg)
    for (n, p), (_, tp_) in zip(model.named_parameters(),
                                twin.named_parameters()):
        v = p._value
        if v.ndim == 2 and any(s in n for s in _QUANT_PATTERNS):
            codes, scales = quantize_array(v, bits, group_size)
            tp_._value = dequantize_array(codes, scales, bits,
                                          in_features=int(v.shape[0]),
                                          out_dtype=v.dtype)
        else:
            tp_._value = v
    for (_, b), (_, tb) in zip(model.named_buffers(),
                               twin.named_buffers()):
        tb._value = b._value
    return twin


@pytest.fixture(scope="module")
def setup():
    """One model + its host-dequantized int8 twin for the whole file."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    return model, _dequant_twin(model, cfg, 8), cfg


def _ref(model, prompt, max_new, **kw):
    return model.generate(paddle.to_tensor(prompt[None, :]),
                          max_new_tokens=max_new, **kw).numpy()[0]


def _stream(engine, prompts, max_new=6, **submit_kw):
    engine.reset()
    srv = Server(engine)
    rids = [srv.submit(p, max_new_tokens=max_new, **submit_kw)
            for p in prompts]
    res = srv.run_until_idle()
    return [res[r] for r in rids]


def _prompts(cfg, seed, lens):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
            for L in lens]


# ---------------------------------------------------------------------------
# int8 KV: in-read dequant vs the dequant-then-dense oracle
# ---------------------------------------------------------------------------

class TestInt8KVInRead:
    def _arena(self, seed=0):
        from paddle_tpu.ops.pallas import paged_attention as pa
        rs = np.random.RandomState(seed)
        S, MB, BS, KVH, G, D, NB = 3, 4, 8, 2, 2, 16, 16
        H = KVH * G
        q = jnp.asarray(rs.randn(S, H, D).astype(np.float32))
        kc, ks = pa.quantize_kv(
            jnp.asarray(3 * rs.randn(NB, BS, KVH, D).astype(np.float32)))
        vc, vs = pa.quantize_kv(
            jnp.asarray(rs.randn(NB, BS, KVH, D).astype(np.float32)))
        tbl = jnp.asarray(rs.randint(1, NB, (S, MB)).astype(np.int32))
        lens = jnp.asarray([5, 17, 32], jnp.int32)
        return q, kc, vc, ks, vs, tbl, lens, D

    def test_cpu_fallback_matches_oracle(self):
        """The per-block scan fallback (what the whole CPU lane runs)
        matches the dequant-then-dense oracle: same quantized inputs,
        fp32 accumulation reassociated by the online softmax."""
        from paddle_tpu.ops.pallas import paged_attention as pa
        q, kc, vc, ks, vs, tbl, lens, D = self._arena()
        ref = pa.paged_attention_int8_reference(
            q[:, None], kc, vc, ks, vs, tbl, lens, scale=D ** -0.5)[:, 0]
        out = pa._int8_decode_fallback(q, kc, vc, ks, vs, tbl, lens,
                                       scale=D ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    @pytest.mark.parametrize("case", sorted(WALK_CASES))
    def test_interpret_kernel_matches_oracle(self, case, monkeypatch):
        """The Pallas int8 kernel (interpret mode on CPU) walks the same
        live pages as the bf16 one, applies each row's absmax / 127 step
        to its column of the scores and the probabilities, and matches
        the dequant-then-dense oracle at every edge of the walk
        (tests/paged_walk_cases.py), GQA heads and dead slots included."""
        pytest.importorskip("jax.experimental.pallas")
        import paddle_tpu.ops.pallas.fused as fused
        from paddle_tpu.ops.pallas import paged_attention as pa
        monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
        monkeypatch.setattr(pa, "_CHUNK_ROWS", chunk_rows())
        q, ka, va, tbl, lens = walk_inputs(case, seed=1)
        (kc, ks), (vc, vs) = pa.quantize_kv(ka), pa.quantize_kv(va)
        assert pa._kernel_ok_int8(kc)
        scale = q.shape[-1] ** -0.5
        out = pa.paged_attention_decode_int8(q, kc, vc, ks, vs, tbl,
                                             lens, scale=scale)
        ref = pa.paged_attention_int8_reference(
            q[:, None], kc, vc, ks, vs, tbl, lens, scale=scale)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_int8_kernel_not_dispatched_on_cpu(self):
        """Off-TPU the int8 read must take the per-block fallback (the
        no-fp32-transient lane), never the kernel."""
        import paddle_tpu.ops.pallas.fused as fused
        from paddle_tpu.ops.pallas.paged_attention import _kernel_ok_int8
        if jax.default_backend() == "cpu" and not fused._FORCE_INTERPRET:
            assert not _kernel_ok_int8(jnp.zeros((4, 8, 2, 16), jnp.int8))

    def test_int8_engine_stream_matches_oracle_route(self, setup,
                                                     monkeypatch):
        """Greedy int8-KV engine streams are token-identical whether
        the decode read runs the in-read path (production) or the
        dequant-then-dense oracle — 'within the queryable bound' made
        concrete: the ~1e-6 softmax reassociation never flips argmax on
        this stream."""
        from paddle_tpu.ops.pallas import paged_attention as pa
        model, _, cfg = setup
        e8 = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            block_size=8, prefill_chunk=8, kv_int8=True)
        prompts = _prompts(cfg, 3, (5, 9, 12))
        got = _stream(e8, prompts)
        monkeypatch.setattr(pa, "_FORCE_INT8_REFERENCE", True)
        # fresh engine: the production program is already compiled on
        # e8's backend; the oracle route must trace its own
        e8_ref = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            block_size=8, prefill_chunk=8, kv_int8=True)
        ref = _stream(e8_ref, prompts)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)
        e8.manager.assert_consistent()

    def test_quantized_decode_holds_no_dense_fp32_kv(self, setup):
        """Recursive jaxpr walk over the int8 engine's ONE decode-block
        program: no fp32 intermediate of the arena shape
        (num_blocks, block_size, kvh, d) — a whole-arena dequant — and
        none of the gathered per-slot dense shapes
        (S, T, kvh, d) / (S, mb, bs, kvh, d) — the PR 4 transient this
        PR exists to kill. The fp32 engine's program, by contrast, DOES
        read dense-shaped fp32 (sanity that the walk can see one)."""
        from jax.extend.core import ClosedJaxpr, Jaxpr

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for v in eqn.params.values():
                    if isinstance(v, ClosedJaxpr):
                        yield from walk(v.jaxpr)
                    elif isinstance(v, Jaxpr):
                        yield from walk(v)

        def fp32_shapes(engine):
            back = engine.backend
            from paddle_tpu.serving.engine import build_slot_block_fn
            fn = build_slot_block_fn(back._pure, engine.decode_block)
            closed = jax.make_jaxpr(fn)(
                back._pv, back._bv, engine._cache, engine._state)
            shapes = set()
            for eqn in walk(closed.jaxpr):
                for v in list(eqn.outvars) + list(eqn.invars):
                    aval = getattr(v, "aval", None)
                    if aval is not None and \
                            getattr(aval, "dtype", None) == jnp.float32:
                        shapes.add(tuple(aval.shape))
            return shapes

        model, _, cfg = setup
        S, bs = 2, 8
        e8 = ContinuousBatchingEngine(
            model, num_slots=S, max_len=64, decode_block=4, paged=True,
            block_size=bs, prefill_chunk=8, kv_int8=True)
        nb = e8.num_kv_blocks
        mb = e8.max_blocks
        kvh = cfg.num_key_value_heads
        d = cfg.hidden_size // cfg.num_attention_heads
        banned = {(nb, bs, kvh, d),                  # full-arena dequant
                  (S, mb * bs, kvh, d),              # gathered dense
                  (S, mb, bs, kvh, d)}               # pre-reshape gather
        got = fp32_shapes(e8)
        assert not (got & banned), \
            f"quantized decode materializes dense fp32 KV: {got & banned}"
        # sanity: the walk sees the fp32 engine's dense arena reads
        efp = ContinuousBatchingEngine(
            model, num_slots=S, max_len=64, decode_block=4, paged=True,
            block_size=bs, prefill_chunk=8)
        assert (e8.num_kv_blocks, bs, kvh, d) in fp32_shapes(efp)

    def test_fp32_mode_untouched_bit_identical(self, setup):
        """fp32-mode paged streams stay bit-identical to generate() —
        the in-read int8 path must not perturb the fp32 route."""
        model, _, cfg = setup
        engine = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            block_size=8, prefill_chunk=8)
        prompts = _prompts(cfg, 4, (5, 9))
        for got, p in zip(_stream(engine, prompts), prompts):
            np.testing.assert_array_equal(
                got, _ref(model, p, 6, temperature=0.0))


# ---------------------------------------------------------------------------
# weight-only int8/int4 serving
# ---------------------------------------------------------------------------

class TestWeightOnlyServing:
    def test_int8_dense_stream_bit_identical_to_dequant_twin(self,
                                                             setup):
        """The quant engine's greedy stream equals generate() on the
        host-dequantized twin BIT-FOR-BIT (in-graph dequant is the same
        math), with the compile count pinned at 1."""
        model, twin, cfg = setup
        eng = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4,
            quant="int8")
        prompts = _prompts(cfg, 5, (5, 9, 12))
        for got, p in zip(_stream(eng, prompts), prompts):
            np.testing.assert_array_equal(
                got, _ref(twin, p, 6, temperature=0.0))
        assert eng.decode_compile_count() == 1
        assert 0.0 < eng.weight_error_bound() < 0.1
        b = eng.quant_error_bound()
        assert b["kv"] == 0.0 and b["weights"] > 0.0

    def test_sampled_stream_matches_twin_seed(self, setup):
        """Seeded sampling rides the same key schedule through the
        quantized block — parity with the twin's generate(seed)."""
        model, twin, cfg = setup
        eng = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4,
            quant=QuantConfig(weights="int8"))
        p = _prompts(cfg, 6, (9,))[0]
        got = _stream(eng, [p], temperature=1.0, top_k=50, seed=7)[0]
        np.testing.assert_array_equal(
            got, _ref(twin, p, 6, do_sample=True, temperature=1.0,
                      top_k=50, seed=7))

    def test_paged_kv_int8_plus_weight_int8(self, setup):
        """The fully quantized stack (int8 arena + int8 weights) serves
        with both bounds positive, ONE decode + ONE chunk program, and
        ~3x fewer bytes per decode step than the fp32 paged engine."""
        model, _, cfg = setup
        q8 = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            block_size=8, prefill_chunk=8, kv_int8=True, quant="int8")
        fp = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, paged=True,
            block_size=8, prefill_chunk=8)
        prompts = _prompts(cfg, 7, (5, 9))
        got = _stream(q8, prompts)
        assert [g.shape for g in got] == [(11,), (15,)]
        assert q8.decode_compile_count() == 1
        assert q8.prefill_compile_count() == 1
        b = q8.quant_error_bound()
        assert b["kv"] > 0.0 and b["weights"] > 0.0
        assert fp.decode_bytes_per_step()["total"] \
            > 2.5 * q8.decode_bytes_per_step()["total"]
        q8.manager.assert_consistent()

    def test_int4_grouped_stream_matches_dequant_twin(self, setup):
        """int4 weights with per-group scales: the serving stream
        equals generate() on a twin dequantized with the SAME grouped
        recipe, and the int4 bound is looser than int8's."""
        model, _, cfg = setup
        gcfg = QuantConfig(weights="int4", group_size=32)
        twin4 = _dequant_twin(model, cfg, 4, 32)
        eng = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, quant=gcfg)
        prompts = _prompts(cfg, 8, (5, 9))
        for got, p in zip(_stream(eng, prompts), prompts):
            np.testing.assert_array_equal(
                got, _ref(twin4, p, 6, temperature=0.0))
        e8 = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, quant="int8")
        assert eng.weight_error_bound() > e8.weight_error_bound()

    def test_spec_quant_stream_matches_plain_quant(self, setup):
        """spec= composes with quant=: the draft-verify engine on
        quantized weights emits the same greedy stream as the plain
        quant engine (the verify head dequantizes the same codes)."""
        model, _, cfg = setup
        plain = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, quant="int8")
        spec = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4,
            spec=SpecConfig(k=4), quant="int8")
        assert isinstance(spec, SpecPagedEngine)
        prompts = _prompts(cfg, 9, (5, 9))
        a = _stream(plain, prompts, max_new=8)
        b = _stream(spec, prompts, max_new=8)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        assert spec.decode_compile_count() == 1

    def test_bytes_read_accounting_in_registry(self, setup):
        """The decode dispatch notes bytes-read/step into
        pt_serving_decode_bytes_read_total, and the quant engine's rate
        sits well under the fp32 engine's."""
        from paddle_tpu.observability import metrics
        model, _, cfg = setup
        fp = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                      decode_block=4)
        q8 = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                      decode_block=4, quant="int8")
        prompts = _prompts(cfg, 10, (5,))
        prev = metrics.enabled()
        metrics.enable(True)
        try:
            c = metrics.REGISTRY.get(
                "pt_serving_decode_bytes_read_total")
            b0 = c.value()
            _stream(fp, prompts)
            per_fp = (c.value() - b0) / max(fp.steps, 1)
            b0 = c.value()
            _stream(q8, prompts)
            per_q8 = (c.value() - b0) / max(q8.steps, 1)
            # the bound gauges refresh on quant_error_bound()
            q8.quant_error_bound()
            g = metrics.REGISTRY.get("pt_serving_weight_error_bound")
            assert g.value() > 0.0
        finally:
            metrics.enable(prev)
        assert per_fp > 0 and per_q8 > 0
        assert per_fp > 1.5 * per_q8

    def test_bound_gauges_registered_at_import(self):
        """Catalog-complete-at-zero: both quant gauges exist in the
        registry without any engine having been built in this process
        path (registered at serving import)."""
        from paddle_tpu.observability.metrics import REGISTRY
        for fam in ("pt_serving_kv_error_bound",
                    "pt_serving_weight_error_bound",
                    "pt_serving_decode_bytes_read_total"):
            assert REGISTRY.get(fam) is not None, fam

    def test_weight_bound_dominates_measured_error(self, setup):
        """|dequant - fp32| of every quantized weight sits under the
        queryable bound (half the worst quantization step)."""
        model, _, _ = setup
        eng = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, quant="int8")
        bound = eng.weight_error_bound()
        named = list(model.named_parameters())
        back = eng.backend
        for i, meta in back._qmeta.items():
            codes, scales = back._pv[i]
            deq = dequantize_array(codes, scales, meta.bits,
                                   in_features=meta.in_features)
            err = float(jnp.max(jnp.abs(deq - named[i][1]._value)))
            assert err <= bound + 1e-7


# ---------------------------------------------------------------------------
# every composition of the engine factory over one stream
# ---------------------------------------------------------------------------

_PAGED = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)
# name -> (factory arguments, the class the factory must build, bits of the
# dequantized twin generate() runs on or None for the model itself)
COMPOSITIONS = {
    "paged": (_PAGED, ContinuousBatchingEngine, None),
    "paged-kv_int8": (dict(_PAGED, kv_int8=True), ContinuousBatchingEngine,
                      None),
    "paged-kv_int8-w_int8": (dict(_PAGED, kv_int8=True,
                                  quant=QuantConfig(weights="int8")),
                             ContinuousBatchingEngine, 8),
    "paged-w_int4": (dict(_PAGED, quant=QuantConfig(weights="int4")),
                     ContinuousBatchingEngine, 4),
    "paged-spec_k8": (dict(_PAGED, max_len=96, spec=SpecConfig(k=8)),
                      SpecPagedEngine, None),
}


@pytest.mark.parametrize("name", list(COMPOSITIONS))
def test_every_composition_serves_the_same_stream(setup, name, monkeypatch):
    """The ONE test that takes every composition the
    ``ContinuousBatchingEngine`` factory builds over the same ragged
    5 / 9 / 12 stream: the factory builds the class it should, the decode
    program compiles once, and the tokens are the reference's. Lossless
    compositions (and weight-only quant against its dequantized twin)
    equal per-request ``generate()`` bit for bit; an int8 arena is lossy,
    so its stream is held to the same composition read through the
    dequant-then-dense oracle, token for token."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    model, twin8, cfg = setup
    kw, cls, bits = COMPOSITIONS[name]
    eng = ContinuousBatchingEngine(model, **kw)
    assert type(eng) is cls
    prompts = _prompts(cfg, 1, (5, 9, 12))
    got = _stream(eng, prompts, max_new=5)
    assert eng.decode_compile_count() == 1
    if kw.get("kv_int8"):
        assert eng.kv_error_bound() > 0.0
        monkeypatch.setattr(pa, "_FORCE_INT8_REFERENCE", True)
        ref = _stream(ContinuousBatchingEngine(model, **kw), prompts,
                      max_new=5)
    else:
        oracle = model if bits is None else twin8 if bits == 8 \
            else _dequant_twin(model, cfg, bits)
        ref = [_ref(oracle, p, 5, temperature=0.0) for p in prompts]
    for p, a, b in zip(prompts, got, ref):
        assert a.shape == (len(p) + 5,)
        np.testing.assert_array_equal(a, b)
    assert eng.prefill_compile_count() == 1
    eng.manager.assert_consistent()


# ---------------------------------------------------------------------------
# tensor-parallel composition
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 (simulated) devices for the 2x4 mesh")
class TestTPQuant:
    def test_exact_mode_sharded_quant_bit_identical(self):
        """quant= composes with tp mode='exact': per-shard scales ride
        the weight PartitionSpecs (column-sharded weights' per-channel
        scales split on the out dim), and the sharded quantized stream
        is BIT-IDENTICAL to the 1-chip quant engine; mode='psum' +
        quant refuses loudly."""
        from paddle_tpu.distributed.mesh import build_device_mesh
        from paddle_tpu.serving import TPConfig
        paddle.seed(0)
        cfg = llama_tiny_config(num_attention_heads=8,
                                num_key_value_heads=8)
        model = LlamaForCausalLM(cfg)
        mesh = build_device_mesh({"dp": 2, "mp": 4})
        one = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, quant="int8")
        tp = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4, quant="int8",
            tp=TPConfig(axes=("dp", "mp"), mesh=mesh))
        prompts = _prompts(cfg, 12, (5, 9))
        a, b = _stream(one, prompts), _stream(tp, prompts)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        assert tp.tp_degree() == 8
        assert tp.weight_error_bound() == one.weight_error_bound() > 0
        with pytest.raises(NotImplementedError, match="psum"):
            ContinuousBatchingEngine(
                model, num_slots=2, max_len=64, decode_block=4,
                quant="int8",
                tp=TPConfig(axes=("dp", "mp"), mode="psum", mesh=mesh))


# ---------------------------------------------------------------------------
# routing matrix
# ---------------------------------------------------------------------------

class TestQuantRouting:
    def test_env_flag_never_reroutes_explicit_backend(self, setup,
                                                      monkeypatch):
        """PT_SERVING_QUANT_WEIGHTS opts IN new engine builds only: a
        caller holding an explicit backend keeps its fp32 weights."""
        model, _, cfg = setup
        backend = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                           decode_block=4).backend
        monkeypatch.setenv("PT_SERVING_QUANT_WEIGHTS", "int8")
        eng = ContinuousBatchingEngine(backend=backend)
        assert eng.backend.quant_cfg is None
        assert eng.weight_error_bound() == 0.0
        # ...while a model build under the same env DOES quantize
        eng2 = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                        decode_block=4)
        assert eng2.backend.quant_cfg is not None
        assert eng2.weight_error_bound() > 0.0

    def test_quant_with_explicit_backend_refused(self, setup):
        model, _, cfg = setup
        plain = ContinuousBatchingEngine(
            model, num_slots=2, max_len=64, decode_block=4,
            block_size=8, prefill_chunk=8)
        for quant in ("int8", QuantConfig()):
            with pytest.raises(ValueError, match="explicit backend"):
                ContinuousBatchingEngine(backend=plain.backend,
                                         quant=quant)
        # quant=False against a QUANTIZED backend refuses too: the
        # codes are baked in — silently serving quantized weights to a
        # caller who pinned fp32 would be the inverse misconfiguration
        qb = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                      decode_block=4, quant="int8")
        with pytest.raises(ValueError, match="explicit backend"):
            ContinuousBatchingEngine(backend=qb.backend, quant=False)

    def test_invalid_configs_refused_loudly(self, setup):
        model, _, cfg = setup
        with pytest.raises(ValueError, match="int8"):
            QuantConfig(weights="fp8")
        with pytest.raises(ValueError, match="group_size"):
            QuantConfig(group_size=0)
        with pytest.raises(ValueError, match="QuantConfig"):
            resolve_quant_config(42)
        # group_size must divide every quantized weight's in_features
        with pytest.raises(ValueError, match="does not divide"):
            ContinuousBatchingEngine(
                model, num_slots=2, max_len=64, decode_block=4,
                quant=QuantConfig(weights="int8", group_size=48))

    def test_env_knob_routes_through_flags(self, setup, monkeypatch):
        monkeypatch.setenv("PT_SERVING_QUANT_WEIGHTS", "int4")
        monkeypatch.setenv("PT_SERVING_QUANT_GROUP", "32")
        cfg = resolve_quant_config(None)
        assert cfg == QuantConfig(weights="int4", group_size=32)
        monkeypatch.setenv("PT_SERVING_QUANT_WEIGHTS", "")
        assert resolve_quant_config(None) is None
        monkeypatch.delenv("PT_SERVING_QUANT_WEIGHTS")
        assert resolve_quant_config(None) is None
        assert resolve_quant_config("int8") == QuantConfig()
        assert resolve_quant_config(False) is None

    def test_direct_paged_ctor_honors_quant(self, setup):
        """SpecPagedEngine(model, ..., quant=...) — a subclass's direct
        constructor, which the factory does not route — quantizes like
        the factory (same contract as kv_int8)."""
        model, _, cfg = setup
        eng = SpecPagedEngine(model, num_slots=2, max_len=64,
                              decode_block=4, block_size=8,
                              prefill_chunk=8, spec=SpecConfig(k=2),
                              quant="int8")
        assert eng.weight_error_bound() > 0.0
        prompts = _prompts(cfg, 11, (5,))
        assert _stream(eng, prompts)[0].shape == (11,)
        eng.manager.assert_consistent()
