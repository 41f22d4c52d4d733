"""jaxpr pass infrastructure (reference: pir PassManager + pattern
rewriter, inference conv_bn_fuse_pass — SURVEY §2.1 'PIR + passes')."""
import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.passes import (PassManager, apply_passes, dce_pass,
                               fold_constants, program_stats,
                               fuse_conv_bn)


class TestJaxprPasses:
    def _trace(self, f, *args):
        return jax.make_jaxpr(f)(*args)

    def test_dce_removes_dead_eqns(self):
        def f(x):
            dead = jnp.exp(x) + 5.0      # never used
            return x * 2.0
        closed = self._trace(f, jnp.ones(3))
        before = program_stats(closed)["n_eqns"]
        after = program_stats(dce_pass(closed))["n_eqns"]
        assert after < before
        out = apply_passes(f, jnp.ones(3), passes=[dce_pass])(
            jnp.ones(3))
        np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(3))

    def test_dce_preserves_semantics_under_jit(self):
        def f(x, y):
            a = x @ y
            unused = jnp.sin(a).sum()
            return jnp.tanh(a)
        x = jnp.ones((3, 4)); y = jnp.ones((4, 2))
        g = apply_passes(f, x, y, passes=[dce_pass])
        np.testing.assert_allclose(np.asarray(jax.jit(g)(x, y)),
                                   np.asarray(f(x, y)), rtol=1e-6)

    def test_constant_folding(self):
        def f(x):
            w = jnp.sin(jnp.float32(2.0))   # foldable at trace time
            return x * w
        closed = self._trace(f, jnp.ones(3))
        folded = fold_constants(closed)
        assert program_stats(folded)["primitives"].get("sin", 0) == 0
        out = jax.core.eval_jaxpr(folded.jaxpr, folded.consts,
                                  jnp.ones(3))[0]
        np.testing.assert_allclose(np.asarray(out),
                                   np.sin(2.0) * np.ones(3), rtol=1e-6)

    def test_pass_manager_pipeline(self):
        def f(x):
            dead = x + 1.0
            w = jnp.exp(jnp.float32(0.0))
            return x * w
        closed = self._trace(f, jnp.ones(2))
        pm = PassManager([fold_constants, dce_pass])
        out_closed = pm(closed)
        stats = program_stats(out_closed)
        assert stats["primitives"].get("exp", 0) == 0
        assert stats["primitives"].get("add", 0) == 0


class TestConvBnFuse:
    def test_fused_matches_unfused_eval(self):
        paddle.seed(0)
        m = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1),
                          nn.BatchNorm2D(8), nn.ReLU(),
                          nn.Conv2D(8, 4, 3, padding=1),
                          nn.BatchNorm2D(4))
        # train a few steps so BN stats are non-trivial
        from paddle_tpu import optimizer
        opt = optimizer.SGD(learning_rate=0.01,
                            parameters=m.parameters())
        rs = np.random.RandomState(0)
        for _ in range(3):
            x = paddle.to_tensor(rs.rand(4, 3, 8, 8).astype("float32"))
            loss = (m(x) ** 2).mean()
            loss.backward(); opt.step(); opt.clear_grad()
        m.eval()
        x = paddle.to_tensor(rs.rand(2, 3, 8, 8).astype("float32"))
        ref = m(x).numpy()
        fuse_conv_bn(m)
        np.testing.assert_allclose(m(x).numpy(), ref, rtol=1e-4,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# PR 3: pattern matcher + CSE + cascaded-reduction fusion
# ---------------------------------------------------------------------------

from jax.extend.core import ClosedJaxpr, Jaxpr, Var  # noqa: E402

from paddle_tpu.passes import (cse_pass, default_pipeline, fusion_pass,  # noqa: E402
                               inline_pjit)
from paddle_tpu.passes.patterns import (Bind, Capture, EqnGraph, Lit,  # noqa: E402
                                        MatchState, Prim)


def _eval(closed, *args):
    out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)
    return out[0] if len(out) == 1 else tuple(out)


def _walk_eqns(jaxpr):
    """All eqns including nested call/scan/custom-vjp bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            if isinstance(v, ClosedJaxpr):
                yield from _walk_eqns(v.jaxpr)
            elif isinstance(v, Jaxpr):
                yield from _walk_eqns(v)


class TestPatternMatcher:
    def _graph(self, f, *args):
        closed = jax.make_jaxpr(f)(*args)
        return closed, EqnGraph(closed.jaxpr)

    def test_prim_matches_producer_chain(self):
        closed, g = self._graph(lambda x: jnp.exp(x) * 2.0, jnp.ones(3))
        root = closed.jaxpr.eqns[-1]
        st = MatchState()
        pat = Prim("mul", Prim("exp", Capture("x")), Lit(2.0))
        assert pat.match(g, root.outvars[0], st)
        assert st.bindings["x"] is closed.jaxpr.invars[0]

    def test_prim_rejects_wrong_primitive_and_literal(self):
        closed, g = self._graph(lambda x: jnp.exp(x) * 2.0, jnp.ones(3))
        root = closed.jaxpr.eqns[-1]
        assert not Prim("mul", Prim("sin", Capture("x")),
                        Lit(2.0)).match(g, root.outvars[0], MatchState())
        assert not Prim("mul", Prim("exp", Capture("x")),
                        Lit(3.0)).match(g, root.outvars[0], MatchState())

    def test_capture_identity_across_occurrences(self):
        # x*x matches mul(c, c); x*y must not
        closed, g = self._graph(lambda x: x * x, jnp.ones(3))
        pat = Prim("mul", Capture("a"), Capture("a"))
        assert pat.match(g, closed.jaxpr.eqns[-1].outvars[0], MatchState())
        closed2, g2 = self._graph(lambda x, y: x * y,
                                  jnp.ones(3), jnp.ones(3))
        assert not pat.match(g2, closed2.jaxpr.eqns[-1].outvars[0],
                             MatchState())

    def test_capture_skips_broadcast(self):
        def f(x, w):
            return x * w[None, :]
        closed, g = self._graph(f, jnp.ones((2, 3)), jnp.ones(3))
        st = MatchState()
        assert Prim("mul", Capture("x"), Capture("w")).match(
            g, closed.jaxpr.eqns[-1].outvars[0], st)
        # w bound to the PRE-broadcast invar
        assert st.bindings["w"] is closed.jaxpr.invars[1]

    def test_bind_subpattern_identity(self):
        # softmax shape: div(e, sum(e)) with ONE exp
        def f(x):
            e = jnp.exp(x)
            return e / jnp.sum(e, axis=-1, keepdims=True)
        closed, g = self._graph(f, jnp.ones((2, 3)))
        pat = Prim("div", Bind("e", Prim("exp", Capture("x"))),
                   Prim("reduce_sum", Bind("e", Prim("exp", Capture("x")))))
        assert pat.match(g, closed.jaxpr.eqns[-1].outvars[0], MatchState())

        def f2(x):   # two DIFFERENT exps of different inputs
            return jnp.exp(x) / jnp.sum(jnp.exp(x * 2), axis=-1,
                                        keepdims=True)
        closed2, g2 = self._graph(f2, jnp.ones((2, 3)))
        assert not pat.match(g2, closed2.jaxpr.eqns[-1].outvars[0],
                             MatchState())


class TestInlinePjit:
    def test_log_softmax_pjit_inlined_semantics_identical(self):
        def f(x):
            return jax.nn.log_softmax(x, axis=-1) * 2.0
        x = jnp.asarray(np.random.RandomState(0).randn(4, 8), jnp.float32)
        closed = jax.make_jaxpr(f)(x)
        assert any(e.primitive.name == "jit" for e in closed.jaxpr.eqns)
        inlined = inline_pjit(closed)
        assert not any(e.primitive.name == "jit"
                       for e in inlined.jaxpr.eqns)
        np.testing.assert_array_equal(np.asarray(_eval(inlined, x)),
                                      np.asarray(f(x)))

    def test_nested_pjit_inlined_to_fixpoint(self):
        def f(x):
            return jnp.var(x, axis=-1)     # pjit(_var) contains _where
        x = jnp.asarray(np.random.RandomState(0).randn(4, 8), jnp.float32)
        inlined = inline_pjit(jax.make_jaxpr(f)(x))
        assert not any(e.primitive.name == "jit"
                       for e in inlined.jaxpr.eqns)
        np.testing.assert_allclose(np.asarray(_eval(inlined, x)),
                                   np.asarray(f(x)), rtol=1e-6)


class TestCse:
    def test_duplicate_chains_merge_bit_identical(self):
        def f(x):
            a = jnp.exp(x) + jnp.sum(jnp.exp(x))
            b = jnp.exp(x) * 3.0
            return a + b
        x = jnp.asarray(np.random.RandomState(0).randn(8), jnp.float32)
        closed = jax.make_jaxpr(f)(x)
        deduped = cse_pass(closed)
        n_exp = sum(1 for e in deduped.jaxpr.eqns
                    if e.primitive.name == "exp")
        assert n_exp == 1
        np.testing.assert_array_equal(np.asarray(_eval(deduped, x)),
                                      np.asarray(f(x)))

    def test_literal_operands_key_by_value(self):
        def f(x):
            return x / 8.0 + jnp.sum(x) / 8.0   # two div-by-8 eqns differ
        x = jnp.ones(4)
        deduped = cse_pass(jax.make_jaxpr(f)(x))
        # different first operands: both divs must SURVIVE
        assert sum(1 for e in deduped.jaxpr.eqns
                   if e.primitive.name == "div") == 2
        np.testing.assert_array_equal(np.asarray(_eval(deduped, x)),
                                      np.asarray(f(x)))

    def test_cse_rewrites_outvars(self):
        def f(x):
            return jnp.sin(x), jnp.sin(x)
        x = jnp.ones(3)
        deduped = cse_pass(jax.make_jaxpr(f)(x))
        assert sum(1 for e in deduped.jaxpr.eqns
                   if e.primitive.name == "sin") == 1
        a, b = _eval(deduped, x)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFoldConstantsConstvars:
    def test_nonscalar_fold_becomes_constvar(self):
        """Regression: a folded NON-SCALAR feeding a live eqn used to
        leave a dangling var (its producer dropped, value never spliced
        because only scalars became Literals)."""
        c = jnp.arange(4, dtype=jnp.float32)

        def f(x):
            return x + jnp.exp(c)          # exp(const vector) folds
        x = jnp.ones(4)
        closed = jax.make_jaxpr(f)(x)
        folded = fold_constants(closed)
        assert not any(e.primitive.name == "exp"
                       for e in folded.jaxpr.eqns)
        # every eqn input is produced/bound — eval proves the splice
        np.testing.assert_allclose(np.asarray(_eval(folded, x)),
                                   np.asarray(f(x)), rtol=1e-6)

    def test_fold_feeding_outvar_becomes_constvar(self):
        c = jnp.arange(3, dtype=jnp.float32)

        def f(x):
            return jnp.exp(c), x * 2.0     # folded value IS an output
        x = jnp.ones(3)
        folded = fold_constants(jax.make_jaxpr(f)(x))
        a, b = _eval(folded, x)
        np.testing.assert_allclose(np.asarray(a), np.exp(np.arange(3)),
                                   rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(b), 2.0 * np.ones(3))

    def test_scalar_fold_still_splices_literal(self):
        def f(x):
            return x * jnp.sin(jnp.float32(2.0))
        x = jnp.ones(3)
        folded = fold_constants(jax.make_jaxpr(f)(x))
        assert not any(e.primitive.name == "sin"
                       for e in folded.jaxpr.eqns)
        np.testing.assert_allclose(np.asarray(_eval(folded, x)),
                                   np.sin(2.0) * np.ones(3), rtol=1e-6)


class TestReductionFusion:
    def _run_pipeline(self, f, *args):
        closed = jax.make_jaxpr(f)(*args)
        out = PassManager(default_pipeline()).run(closed)
        return out, dict(fusion_pass.last_rewrites)

    def test_softmax_rewritten_and_matches(self):
        def f(x):
            m = jnp.max(x, axis=-1, keepdims=True)
            e = jnp.exp(x - m)
            return e / jnp.sum(e, axis=-1, keepdims=True)
        x = jnp.asarray(np.random.RandomState(0).randn(4, 16), jnp.float32)
        fused, rewrites = self._run_pipeline(f, x)
        assert rewrites.get("softmax") == 1
        assert any(e.primitive.name == "closed_call"
                   for e in fused.jaxpr.eqns)
        np.testing.assert_allclose(np.asarray(_eval(fused, x)),
                                   np.asarray(f(x)), rtol=1e-6, atol=1e-7)

    def test_log_softmax_rewritten_and_matches(self):
        x = jnp.asarray(np.random.RandomState(1).randn(4, 16), jnp.float32)
        fused, rewrites = self._run_pipeline(
            lambda v: jax.nn.log_softmax(v, axis=-1), x)
        assert rewrites.get("log_softmax") == 1
        np.testing.assert_allclose(
            np.asarray(_eval(fused, x)),
            np.asarray(jax.nn.log_softmax(x, axis=-1)), rtol=1e-6,
            atol=1e-7)

    def test_layer_norm_rewritten_one_pass(self):
        def f(x):
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            return (x - mean) * jax.lax.rsqrt(var + 1e-5)
        x = jnp.asarray(np.random.RandomState(2).randn(8, 32), jnp.float32)
        fused, rewrites = self._run_pipeline(f, x)
        assert rewrites.get("layer_norm") == 1
        # one-pass form: documented tolerance vs the two-pass original
        np.testing.assert_allclose(np.asarray(_eval(fused, x)),
                                   np.asarray(f(x)), rtol=5e-5, atol=5e-6)

    def test_rms_norm_rewritten_to_fused_kernel(self):
        def f(x, w):
            ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                          keepdims=True)
            return (x.astype(jnp.float32)
                    * jax.lax.rsqrt(ms + 1e-6)).astype(x.dtype) * w
        x = jnp.asarray(np.random.RandomState(3).randn(4, 16),
                        jnp.float32).astype(jnp.bfloat16)
        w = jnp.ones(16, jnp.bfloat16)
        fused, rewrites = self._run_pipeline(f, x, w)
        assert rewrites.get("rms_norm") == 1
        np.testing.assert_allclose(
            np.asarray(_eval(fused, x, w)).astype(np.float32),
            np.asarray(f(x, w)).astype(np.float32), rtol=2e-2, atol=2e-2)

    def test_xent_rewritten_grads_match(self):
        vocab = 8192   # > chunk cap so the fallback actually chunks
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(8, vocab), jnp.float32)
        lab = jnp.asarray(rs.randint(0, vocab, (8,)), jnp.int32)

        def f(logits, labels):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[:, None],
                                       axis=1)[:, 0]
            return jnp.mean(nll)
        fused, rewrites = self._run_pipeline(f, x, lab)
        assert rewrites.get("softmax_xent") == 1
        np.testing.assert_allclose(float(_eval(fused, x, lab)),
                                   float(f(x, lab)), rtol=1e-6)
        g_fused = jax.grad(lambda v: _eval(fused, v, lab))(x)
        g_ref = jax.grad(lambda v: f(v, lab))(x)
        np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-7)

    def test_fused_xent_never_materializes_vocab_tensor(self):
        """Acceptance: after fusion, NO equation in the program
        (including nested call/scan bodies) produces an (N, vocab)
        value — the log-prob / one-hot intermediates are gone. The
        unfused program materializes several."""
        vocab = 8192
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(8, vocab), jnp.float32)
        lab = jnp.asarray(rs.randint(0, vocab, (8,)), jnp.int32)

        def f(logits, labels):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[:, None],
                                       axis=1)[:, 0]
            return jnp.mean(nll)

        def vocab_sized(closed):
            return [e.primitive.name for e in _walk_eqns(closed.jaxpr)
                    for o in e.outvars
                    if getattr(o.aval, "shape", None) == (8, vocab)]

        unfused = inline_pjit(jax.make_jaxpr(f)(x, lab))
        assert len(vocab_sized(unfused)) >= 2     # exp + log_softmax sub
        fused, _ = self._run_pipeline(f, x, lab)
        assert vocab_sized(fused) == []

    def test_flag_off_leaves_programs_unchanged(self, monkeypatch):
        """PT_FUSION_PASSES default-off: the traced cross_entropy
        program contains no fused closed_call and no pallas xent."""
        monkeypatch.delenv("PT_FUSION_PASSES", raising=False)
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(6)
        xa = paddle.to_tensor(rs.randn(4, 32).astype("float32"))
        lab = paddle.to_tensor(rs.randint(0, 32, (4,)).astype("int64"))
        out = F.cross_entropy(xa, lab)
        assert out is not None
        # and the fused kernel module is only reached when the flag is on
        from paddle_tpu.passes import fusion_enabled
        assert not fusion_enabled()
        monkeypatch.setenv("PT_FUSION_PASSES", "1")
        assert fusion_enabled()


class TestFusedXentKernel:
    def _data(self, n=12, v=256, seed=0):
        rs = np.random.RandomState(seed)
        x = jnp.asarray(rs.randn(n, v), jnp.float32)
        lab = jnp.asarray(rs.randint(0, v, (n,)), jnp.int32)
        return x, lab

    def test_scan_fallback_matches_reference(self):
        from paddle_tpu.ops.pallas import xent
        x, lab = self._data(v=8192)
        nll, lse = xent.softmax_xent_rows(x, lab)
        rn, rl = xent.softmax_xent_rows_reference(x, lab)
        np.testing.assert_allclose(np.asarray(nll), np.asarray(rn),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(rl),
                                   rtol=1e-6, atol=1e-6)

    def test_pallas_interpret_matches_reference(self):
        from paddle_tpu.ops.pallas import fused, xent
        x, lab = self._data(n=13, v=256, seed=1)   # ragged row count
        fused._FORCE_INTERPRET = True
        try:
            nll, lse = jax.jit(xent.softmax_xent_rows)(x, lab)
        finally:
            fused._FORCE_INTERPRET = False
        rn, rl = xent.softmax_xent_rows_reference(x, lab)
        np.testing.assert_allclose(np.asarray(nll), np.asarray(rn),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(rl),
                                   rtol=1e-5, atol=1e-5)

    def test_pallas_interpret_backward_matches(self):
        from paddle_tpu.ops.pallas import fused, xent
        x, lab = self._data(n=8, v=128, seed=2)
        wrow = jnp.arange(8, dtype=jnp.float32)

        def loss_fused(v):
            nll, lse = xent.softmax_xent_rows(v, lab)
            return jnp.sum(nll * wrow) + 0.5 * jnp.sum(lse)

        def loss_ref(v):
            rn, rl = xent.softmax_xent_rows_reference(v, lab)
            return jnp.sum(rn * wrow) + 0.5 * jnp.sum(rl)
        g_ref = jax.grad(loss_ref)(x)
        fused._FORCE_INTERPRET = True
        try:
            g = jax.grad(loss_fused)(x)
        finally:
            fused._FORCE_INTERPRET = False
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_accumulates_fp32(self):
        from paddle_tpu.ops.pallas import xent
        x, lab = self._data(n=8, v=512, seed=3)
        nll_ref, _ = xent.softmax_xent_rows_reference(x, lab)
        nll_bf, _ = xent.softmax_xent_rows(x.astype(jnp.bfloat16), lab)
        # fp32 accumulation: error bounded by the bf16 INPUT rounding
        np.testing.assert_allclose(np.asarray(nll_bf), np.asarray(nll_ref),
                                   rtol=2e-2, atol=2e-2)


class TestCrossEntropyGatherPath:
    """Satellite: hard-label CE gathers log-probs (no one-hot); the
    fused flag routes the same rows through the one-pass kernel."""

    def _case(self, **kw):
        rs = np.random.RandomState(7)
        logits = paddle.to_tensor(rs.randn(6, 10).astype("float32"))
        labels = paddle.to_tensor(
            np.array([1, 3, 9, 0, -100, 5], np.int64))
        return logits, labels

    def _onehot_ref(self, lg, lb, weight=None, ls=0.0, red="mean"):
        lp = jax.nn.log_softmax(lg, -1)
        oh = jax.nn.one_hot(lb, 10)          # -100 -> zero row
        if ls > 0:
            oh = oh * (1 - ls) + ls / 10
        loss = -jnp.sum(oh * lp, -1)
        valid = lb != -100
        loss = jnp.where(valid, loss, 0.0)
        if weight is not None:
            wt = jnp.take(weight, np.clip(lb, 0, 9))
            loss = loss * wt
            if red == "mean":
                return jnp.sum(loss) / jnp.sum(jnp.where(valid, wt, 0.0))
        if red == "mean":
            return jnp.sum(loss) / jnp.maximum(
                jnp.sum(valid.astype(loss.dtype)), 1.0)
        return jnp.sum(loss) if red == "sum" else loss

    def test_no_one_hot_in_traced_program(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(8)
        x = jnp.asarray(rs.randn(4, 16), jnp.float32)
        lab = jnp.asarray(rs.randint(0, 16, (4,)), jnp.int32)

        def f(xv, lv):
            return F.cross_entropy(paddle.Tensor(xv),
                                   paddle.Tensor(lv))._value
        closed = inline_pjit(jax.make_jaxpr(f)(x, lab))
        # one_hot lowers to eq+convert over an iota: assert no (4, 16)
        # eq/convert chain beyond the log_softmax itself → no iota eqns
        assert not any(e.primitive.name == "iota"
                       for e in _walk_eqns(closed.jaxpr))

    def test_parity_with_onehot_formulation(self):
        import paddle_tpu.nn.functional as F
        logits, labels = self._case()
        lg, lb = logits.numpy(), labels.numpy().astype(np.int32)
        w = paddle.to_tensor((np.random.RandomState(9).rand(10) + 0.5)
                             .astype("float32"))
        for kwargs, ref in [
            ({}, self._onehot_ref(lg, lb)),
            ({"label_smoothing": 0.1}, self._onehot_ref(lg, lb, ls=0.1)),
            ({"reduction": "sum"}, self._onehot_ref(lg, lb, red="sum")),
            ({"reduction": "none"}, self._onehot_ref(lg, lb, red="none")),
            ({"weight": w}, self._onehot_ref(lg, lb, weight=w.numpy())),
        ]:
            got = F.cross_entropy(logits, labels, **kwargs).numpy()
            np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5,
                                       atol=1e-6, err_msg=str(kwargs))

    def test_fused_flag_parity_forward_and_grad(self, monkeypatch):
        import paddle_tpu.nn.functional as F
        logits, labels = self._case()
        lg = logits.numpy()

        def run():
            x = paddle.to_tensor(lg)
            x.stop_gradient = False
            loss = F.cross_entropy(x, labels, label_smoothing=0.1)
            loss.backward()
            return float(loss.numpy()), x.grad.numpy()
        monkeypatch.delenv("PT_FUSION_PASSES", raising=False)
        l0, g0 = run()
        monkeypatch.setenv("PT_FUSION_PASSES", "1")
        l1, g1 = run()
        assert abs(l0 - l1) < 1e-5
        np.testing.assert_allclose(g1, g0, rtol=1e-5, atol=1e-6)


class TestLayerNormOnePass:
    """Satellite: fp32 accumulation on low-precision inputs, one-pass
    mean/var."""

    def test_bf16_numerics_pinned_to_fp32_reference(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(10)
        raw = (rs.randn(8, 64) * 3 + 1).astype(np.float32)
        xb = paddle.to_tensor(raw).astype("bfloat16")
        out = F.layer_norm(xb, 64)
        xf = xb.numpy().astype(np.float32)    # post bf16-rounding input
        m = xf.mean(-1, keepdims=True)
        v = xf.var(-1, keepdims=True)
        want = (xf - m) / np.sqrt(v + 1e-5)
        # stats in fp32: only the I/O rounding (bf16 ~ 2^-8) remains
        np.testing.assert_allclose(out.numpy().astype(np.float32), want,
                                   rtol=2e-2, atol=2e-2)

    def test_fp32_matches_two_pass_reference(self):
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(11)
        x = paddle.to_tensor(rs.randn(4, 32).astype("float32"))
        w = paddle.to_tensor(rs.rand(32).astype("float32"))
        b = paddle.to_tensor(rs.rand(32).astype("float32"))
        out = F.layer_norm(x, 32, weight=w, bias=b).numpy()
        xf = x.numpy()
        m = xf.mean(-1, keepdims=True)
        v = xf.var(-1, keepdims=True)
        want = (xf - m) / np.sqrt(v + 1e-5) * w.numpy() + b.numpy()
        np.testing.assert_allclose(out, want, rtol=5e-5, atol=5e-6)


class TestToStaticPasses:
    def test_to_static_passes_compiles_transformed_program(self):
        from paddle_tpu import jit

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(16, 16)

            def forward(self, x):
                h = self.fc(x)
                return nn.functional.softmax(h, axis=-1).sum() + h.mean()

        paddle.seed(0)
        m = M()
        x = paddle.to_tensor(
            np.random.RandomState(12).randn(4, 16).astype("float32"))
        ref = float(m(x).numpy())
        st = jit.to_static(m.forward, passes=default_pipeline())
        got = float(st(x).numpy())
        assert abs(got - ref) < 1e-5
        stats = st.pass_stats
        assert stats is not None
        assert stats["after"]["n_eqns"] < stats["before"]["n_eqns"]
        assert any(p["pass"] == "fusion" for p in stats["per_pass"])

    def test_to_static_passes_grad(self):
        from paddle_tpu import jit

        def f(x):
            return nn.functional.softmax(x, axis=-1).sum()
        st = jit.to_static(f, passes=default_pipeline())
        x = paddle.to_tensor(
            np.random.RandomState(13).randn(4, 8).astype("float32"))
        x.stop_gradient = False
        loss = st(x)
        loss.backward()
        x2 = paddle.to_tensor(x.numpy())
        x2.stop_gradient = False
        loss2 = f(x2)
        loss2.backward()
        np.testing.assert_allclose(x.grad.numpy(), x2.grad.numpy(),
                                   rtol=1e-5, atol=1e-6)


class TestReviewRegressions:
    def test_layer_norm_large_offset_no_cancellation(self):
        """E[x^2]-E[x]^2 variance catastrophically cancels at
        |mean| >> std; the shifted one-pass form must stay at fp32
        rounding error — in the eager path AND the fusion rewrite."""
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(20)
        raw64 = rs.randn(4, 256) + 1e4
        m = raw64.mean(-1, keepdims=True)
        v = raw64.var(-1, keepdims=True)
        want = (raw64 - m) / np.sqrt(v + 1e-5)
        # eager layer_norm
        out = F.layer_norm(
            paddle.to_tensor(raw64.astype("float32")), 256).numpy()
        np.testing.assert_allclose(out, want, atol=5e-3)
        # fusion-rewritten naive layer_norm

        def naive(x):
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            return (x - mean) * jax.lax.rsqrt(var + 1e-5)
        x = jnp.asarray(raw64, jnp.float32)
        fused = PassManager(default_pipeline()).run(
            jax.make_jaxpr(naive)(x))
        assert fusion_pass.last_rewrites.get("layer_norm") == 1
        got = np.asarray(jax.core.eval_jaxpr(fused.jaxpr, fused.consts,
                                             x)[0])
        np.testing.assert_allclose(got, want, atol=5e-3)

    def test_fusion_matches_constvar_eps(self):
        """eps captured as a traced CONSTVAR (closure jnp scalar, not a
        python float) must still match Lit patterns: fold_constants
        always splices scalar constvars in as Literals, even when
        nothing else folds."""
        eps = jnp.float32(1e-5)   # closure constvar, not a literal

        def naive(x):
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            return (x - mean) * jax.lax.rsqrt(var + eps)
        x = jnp.asarray(np.random.RandomState(21).randn(4, 32),
                        jnp.float32)
        fused = PassManager(default_pipeline()).run(
            jax.make_jaxpr(naive)(x))
        assert fusion_pass.last_rewrites.get("layer_norm") == 1
        out = jax.core.eval_jaxpr(fused.jaxpr, fused.consts, x)[0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(naive(x)),
                                   rtol=5e-5, atol=5e-6)

    def test_capture_never_binds_across_stop_gradient(self):
        """Rewrites must not delete a USER stop_gradient: grads through
        softmax(stop_gradient(x)) stay zero after fusion."""
        def f(x, w):
            return jnp.sum(jax.nn.softmax(
                jax.lax.stop_gradient(x), axis=-1) * w)
        rs = np.random.RandomState(22)
        x = jnp.asarray(rs.randn(4, 8), jnp.float32)
        w = jnp.asarray(rs.randn(4, 8), jnp.float32)
        fused = PassManager(default_pipeline()).run(
            jax.make_jaxpr(f)(x, w))
        # the rewrite may still fire — but on the POST-stop_gradient var
        g = jax.grad(lambda v: jax.core.eval_jaxpr(
            fused.jaxpr, fused.consts, v, w)[0])(x)
        np.testing.assert_array_equal(np.asarray(g), 0.0)
        # and the internal (shift-invariant) stop_gradient skip still
        # lets plain softmax fuse
        plain = PassManager(default_pipeline()).run(
            jax.make_jaxpr(lambda v: jax.nn.softmax(v, axis=-1))(x))
        assert any(e.primitive.name == "closed_call"
                   for e in plain.jaxpr.eqns)

    def test_fused_ce_dtype_matches_unfused(self, monkeypatch):
        """PT_FUSION_PASSES must not change cross_entropy's output
        dtype (bf16 logits, reduction='none')."""
        import paddle_tpu.nn.functional as F
        rs = np.random.RandomState(23)
        lg = paddle.to_tensor(rs.randn(4, 8).astype("float32"))\
            .astype("bfloat16")
        lb = paddle.to_tensor(rs.randint(0, 8, (4,)).astype("int64"))
        monkeypatch.delenv("PT_FUSION_PASSES", raising=False)
        off = F.cross_entropy(lg, lb, reduction="none")
        monkeypatch.setenv("PT_FUSION_PASSES", "1")
        on = F.cross_entropy(lg, lb, reduction="none")
        assert off.dtype == on.dtype
        np.testing.assert_allclose(
            on.numpy().astype(np.float32),
            off.numpy().astype(np.float32), rtol=2e-2, atol=2e-2)

    def test_misaligned_broadcast_never_misfuses(self):
        """A column-normalization on a SQUARE input (shape check can't
        save us) must not match the softmax rule: broadcasts are only
        skipped when keepdims-style (structural) or numpy-trailing
        (bindings)."""
        def colnorm(x):
            m = jnp.max(x, axis=-1, keepdims=True)
            e = jnp.exp(x - m)
            # divides column j by ROW j's sum — not softmax
            return e / jnp.sum(e, axis=-1)[None, :]
        x = jnp.asarray(np.random.RandomState(24).randn(6, 6),
                        jnp.float32)
        fused = PassManager(default_pipeline()).run(
            jax.make_jaxpr(colnorm)(x))
        assert fusion_pass.last_rewrites.get("softmax") is None
        out = jax.core.eval_jaxpr(fused.jaxpr, fused.consts, x)[0]
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(colnorm(x)))

    def test_flag_off_spellings(self, monkeypatch):
        from paddle_tpu.passes import fusion_enabled
        for v in ("off", "no", "0", "false", ""):
            monkeypatch.setenv("PT_FUSION_PASSES", v)
            assert not fusion_enabled(), v
        monkeypatch.setenv("PT_FUSION_PASSES", "1")
        assert fusion_enabled()

    def test_to_static_passes_forwarded_to_dy2static(self):
        """passes= must survive the dy2static fallback: a function with
        tensor control flow still compiles the TRANSFORMED program."""
        from paddle_tpu import jit

        def f(x):
            if (x.sum() > 0):          # tensor bool -> dy2static
                return nn.functional.softmax(x, axis=-1).sum()
            return x.sum()
        st = jit.to_static(f, passes=default_pipeline())
        x = paddle.to_tensor(np.ones((2, 8), np.float32))
        out = st(x)
        assert abs(float(out.numpy()) - 2.0) < 1e-5
        sub = getattr(st, "_dy2static_sub", None)
        assert sub is not None and sub._passes is not None

    def test_to_static_passes_rejects_sot_mode(self):
        from paddle_tpu import jit
        import pytest
        with pytest.raises(ValueError, match="full_graph=True"):
            jit.to_static(lambda x: x, full_graph=False,
                          passes=default_pipeline())
