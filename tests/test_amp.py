"""AMP tests (reference pattern: test/amp/ — verify)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import amp, nn, optimizer


def rnd(*s):
    return np.random.rand(*s).astype(np.float32)


def test_autocast_casts_matmul():
    x = paddle.to_tensor(rnd(4, 4))
    w = paddle.to_tensor(rnd(4, 4))
    with amp.auto_cast(dtype="bfloat16"):
        y = paddle.matmul(x, w)
    assert str(y.dtype) == "bfloat16"
    y2 = paddle.matmul(x, w)
    assert str(y2.dtype) == "float32"


def test_autocast_disabled():
    x = paddle.to_tensor(rnd(2, 2))
    with amp.auto_cast(enable=False):
        assert str(paddle.matmul(x, x).dtype) == "float32"


def test_decorate_o2():
    m = nn.Sequential(nn.Linear(4, 8), nn.LayerNorm(8), nn.Linear(8, 2))
    opt = optimizer.AdamW(parameters=m.parameters())
    m, opt = amp.decorate(m, opt, level="O2", dtype="bfloat16")
    assert str(m[0].weight.dtype) == "bfloat16"
    # norms excluded (kept fp32)
    assert str(m[1].weight.dtype) == "float32"
    assert opt._multi_precision


def test_grad_scaler_scales_and_unscales():
    m = nn.Linear(2, 1)
    opt = optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
    scaler = amp.GradScaler(init_loss_scaling=1024.0)
    x = paddle.to_tensor(rnd(4, 2))
    loss = m(x).sum()
    scaled = scaler.scale(loss)
    np.testing.assert_allclose(scaled.item(), loss.item() * 1024.0,
                               rtol=1e-6)
    scaled.backward()
    w0 = m.weight.numpy().copy()
    scaler.step(opt)
    scaler.update()
    # grads unscaled before the step: step magnitude matches lr*unscaled g
    expect_g = np.broadcast_to(x.numpy().sum(0)[:, None], (2, 1))
    np.testing.assert_allclose(m.weight.numpy(), w0 - 0.1 * expect_g,
                               rtol=1e-4)


def test_grad_scaler_skips_on_inf():
    m = nn.Linear(2, 1)
    opt = optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
    scaler = amp.GradScaler(init_loss_scaling=4.0)
    w0 = m.weight.numpy().copy()
    m.weight.grad = paddle.to_tensor(
        np.array([[np.inf], [1.0]], np.float32))
    scaler.step(opt)
    np.testing.assert_array_equal(m.weight.numpy(), w0)  # step skipped
    assert scaler.get_loss_scaling() < 4.0  # backed off


def test_bf16_training_via_trainstep():
    from paddle_tpu.jit import TrainStep
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = optimizer.AdamW(learning_rate=0.01, parameters=m.parameters())
    m, opt = amp.decorate(m, opt, level="O2", dtype="bfloat16")
    step = TrainStep(m, lambda mm, b: ((mm(b[0]) - b[1]) ** 2).mean(), opt)
    x = rnd(32, 8)
    y = (x.sum(1, keepdims=True) / 4).astype(np.float32)
    first = float(step((paddle.to_tensor(x).astype("bfloat16"),
                        paddle.to_tensor(y).astype("bfloat16"))).item())
    for _ in range(40):
        last = float(step((paddle.to_tensor(x).astype("bfloat16"),
                           paddle.to_tensor(y).astype("bfloat16"))).item())
    assert last < first * 0.5
    assert str(m[0].weight.dtype) == "bfloat16"


class TestOpRegistry:
    """Op-metadata registry (reference: the op YAML single source of
    truth, SURVEY §2.1) — AMP lists are derived from it."""

    def test_registry_covers_op_surface(self):
        from paddle_tpu.ops.registry import all_ops
        ops = all_ops()
        assert len(ops) > 200, len(ops)
        for required in ("matmul", "softmax", "concat", "zeros", "relu"):
            assert required in ops

    def test_metadata_fields(self):
        from paddle_tpu.ops.registry import get_op_meta
        assert get_op_meta("matmul").amp == "white"
        assert get_op_meta("softmax").amp == "black"
        assert get_op_meta("softmax").integer_ok is False
        assert get_op_meta("argmax").differentiable is False
        add = get_op_meta("add")
        if add is not None and add.inplace_variant:
            assert add.inplace_variant == "add_"

    def test_amp_lists_derive_from_registry(self):
        from paddle_tpu import amp
        from paddle_tpu.ops.registry import amp_white_list, amp_black_list
        assert amp.WHITE_LIST == amp_white_list()
        assert amp.BLACK_LIST == amp_black_list()
        assert "matmul" in amp.WHITE_LIST
        assert "layer_norm" in amp.BLACK_LIST

    def test_registered_op_affects_casting_live(self):
        from paddle_tpu import amp
        from paddle_tpu.ops.registry import register_op
        register_op("my_custom_matmul", amp="white")
        assert "my_custom_matmul" in amp.WHITE_LIST


class TestAmpDebugging:
    """paddle.amp.debugging (reference: python/paddle/amp/debugging.py)."""

    def test_check_numerics_modes(self):
        from paddle_tpu.amp import debugging as dbg
        bad = paddle.to_tensor(np.array([1.0, np.nan, np.inf], np.float32))
        with pytest.raises(RuntimeError):
            dbg.check_numerics(bad)
        import warnings
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            dbg.check_numerics(bad,
                               debug_mode=dbg.DebugMode.CHECK_NAN_INF)
        assert len(w) == 1 and "1 NaN and 1 Inf" in str(w[0].message)
        ok = paddle.to_tensor(np.ones((3,), np.float32))
        dbg.check_numerics(ok)     # clean tensor passes silently

    def test_operator_stats_collection(self, capsys):
        from paddle_tpu.amp import debugging as dbg
        with dbg.collect_operator_stats():
            x = paddle.to_tensor(np.ones((2, 2), np.float32))
            _ = (x * 2) + 1
        out = capsys.readouterr().out
        assert "op list" in out and "float32" in out
        # collection is OFF outside the context
        assert not dbg._COLLECTING[0]

    def test_tensor_checker_catches_nan_producing_op(self):
        from paddle_tpu.amp import debugging as dbg
        dbg.enable_tensor_checker(dbg.TensorCheckerConfig())
        try:
            with pytest.raises(RuntimeError):
                paddle.log(paddle.to_tensor([-1.0]))
        finally:
            dbg.disable_tensor_checker()
        # checker off: no raise
        paddle.log(paddle.to_tensor([-1.0]))

    def test_bf16_numerics_and_op_filters(self):
        from paddle_tpu.amp import debugging as dbg
        bad = paddle.to_tensor(
            np.array([1.0, np.nan], np.float32)).astype("bfloat16")
        with pytest.raises(RuntimeError):
            dbg.check_numerics(bad)     # bf16 must not slip through
        dbg.enable_tensor_checker(
            dbg.TensorCheckerConfig(skipped_op_list=["log"]))
        try:
            paddle.log(paddle.to_tensor([-1.0]))    # skipped: no raise
        finally:
            dbg.disable_tensor_checker()
        dbg.enable_tensor_checker(dbg.TensorCheckerConfig())
        try:
            with pytest.raises(RuntimeError):
                paddle.to_tensor([1.0]).fill_(float("inf"))
        finally:
            dbg.disable_tensor_checker()

    def test_tape_gc_single_call_cascade(self):
        from paddle_tpu.tensor import _tape
        # nodes an EARLIER test file on this xdist worker left alive
        # (tests/test_autograd.py keeps two) are not this test's: count
        # from what is there, or the result depends on the files' order
        _tape().gc()
        before = len(_tape().nodes)
        x = paddle.to_tensor([1.0], stop_gradient=False)
        t = ((x * 2) * 3) * 4
        assert len(_tape().nodes) == before + 3
        del t
        _tape().gc()
        assert len(_tape().nodes) == before


def test_autocast_casts_bmm_einsum_addmm():
    # every matmul-class white-list op casts at dispatch, not just matmul
    a = paddle.to_tensor(rnd(2, 3, 4))
    b = paddle.to_tensor(rnd(2, 4, 5))
    m = paddle.to_tensor(rnd(3, 5))
    x = paddle.to_tensor(rnd(3, 4))
    y = paddle.to_tensor(rnd(4, 5))
    with amp.auto_cast(dtype="bfloat16"):
        assert str(paddle.bmm(a, b).dtype) == "bfloat16"
        assert str(paddle.einsum("bij,bjk->bik", a, b).dtype) == "bfloat16"
        assert str(paddle.addmm(m, x, y).dtype) == "bfloat16"
    assert str(paddle.bmm(a, b).dtype) == "float32"


def test_autocast_casts_conv2d():
    x = paddle.to_tensor(rnd(1, 3, 8, 8))
    conv = nn.Conv2D(3, 4, 3)
    with amp.auto_cast(dtype="bfloat16"):
        assert str(conv(x).dtype) == "bfloat16"
    assert str(conv(x).dtype) == "float32"


def test_o2_conv_after_fp32_norm_runs_in_param_dtype():
    # decorate keeps BatchNorm fp32; its fp32 output must not crash (or
    # silently upcast) the next bf16 conv — the conv runs in bf16 and
    # the grad flows (lax.conv demands equal dtypes; VERDICT-era bug)
    m = nn.Sequential(nn.Conv2D(3, 4, 3, padding=1), nn.BatchNorm2D(4),
                      nn.Conv2D(4, 2, 3, padding=1))
    opt = optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
    m, opt = amp.decorate(m, opt, level="O2", dtype="bfloat16")
    x = paddle.to_tensor(rnd(2, 3, 8, 8)).astype("bfloat16")
    out = m(x)
    assert str(out.dtype) == "bfloat16"
    loss = out.astype("float32").sum()
    loss.backward()
    g = m[2].weight.grad
    assert g is not None and np.isfinite(g.astype("float32").numpy()).all()


def test_autocast_custom_black_list_overrides_white_op():
    # a user-black-listed matmul-class op stays fp32 inside auto_cast
    x = paddle.to_tensor(rnd(4, 4))
    with amp.auto_cast(dtype="bfloat16",
                       custom_black_list={"matmul", "conv2d"}):
        assert str(paddle.matmul(x, x).dtype) == "float32"
        conv = nn.Conv2D(3, 4, 3)
        img = paddle.to_tensor(rnd(1, 3, 8, 8))
        assert str(conv(img).dtype) == "float32"
        # non-listed white ops still cast
        assert str(paddle.bmm(x[None], x[None]).dtype) == "bfloat16"


def test_autocast_casts_dot_mv_outer():
    x = paddle.to_tensor(rnd(4, 4))
    v = paddle.to_tensor(rnd(4))
    with amp.auto_cast(dtype="bfloat16"):
        assert str(paddle.dot(v, v).dtype) == "bfloat16"
        assert str(paddle.mv(x, v).dtype) == "bfloat16"
        assert str(paddle.outer(v, v).dtype) == "bfloat16"


def test_autocast_alias_and_role_semantics():
    x = paddle.to_tensor(rnd(4, 4))
    # mm dispatches as the matmul op type: black-listing EITHER name
    # keeps it fp32
    with amp.auto_cast(dtype="bfloat16", custom_black_list={"mm"}):
        assert str(paddle.mm(x, x).dtype) == "float32"
        assert str(paddle.matmul(x, x).dtype) == "bfloat16"
    with amp.auto_cast(dtype="bfloat16", custom_black_list={"matmul"}):
        assert str(paddle.mm(x, x).dtype) == "float32"
    # custom_white_list beats the framework black list
    with amp.auto_cast(dtype="bfloat16"):
        xb = paddle.to_tensor(rnd(4, 4)).astype("bfloat16")
        assert str(paddle.nn.functional.softmax(xb).dtype) == "float32"
    with amp.auto_cast(dtype="bfloat16", custom_white_list={"softmax"}):
        assert str(paddle.nn.functional.softmax(xb).dtype) == "bfloat16"


def test_autocast_linear_integer_passthrough():
    # integer inputs must not be corrupted to bf16 by the white cast
    xi = paddle.to_tensor(np.arange(12, dtype=np.int32).reshape(3, 4) * 100)
    wi = paddle.to_tensor(np.ones((4, 2), np.int32))
    with amp.auto_cast(dtype="bfloat16"):
        out = paddle.nn.functional.linear(xi, wi)
    assert "int" in str(out.dtype)
    np.testing.assert_array_equal(
        out.numpy(), xi.numpy() @ wi.numpy())


def test_autocast_black_conv_over_o2_weights_runs_fp32():
    # black-listed conv in an O2 model upcasts the bf16 weights, not
    # downcasts the fp32 activation
    m = nn.Sequential(nn.Conv2D(3, 4, 3, padding=1), nn.BatchNorm2D(4),
                      nn.Conv2D(4, 2, 3, padding=1))
    amp.decorate(m, level="O2", dtype="bfloat16")
    x = paddle.to_tensor(rnd(1, 3, 8, 8)).astype("bfloat16")
    with amp.auto_cast(dtype="bfloat16", custom_black_list={"conv2d"}):
        out = m(x)
    assert str(out.dtype) == "float32"


def test_black_listed_matmul_upcasts_bf16_inputs():
    # O2-decorated weights are bf16; a black-listed matmul-class op
    # must still run fp32 (upcast), mirroring the conv behavior
    lin = nn.Linear(4, 4)
    amp.decorate(lin, level="O2", dtype="bfloat16")
    x = paddle.to_tensor(rnd(4, 4)).astype("bfloat16")
    with amp.auto_cast(dtype="bfloat16",
                       custom_black_list={"matmul", "linear"}):
        assert str(paddle.matmul(x, x).dtype) == "float32"
        assert str(lin(x).dtype) == "float32"
