"""Hierarchical + quantized collectives (distributed/collectives/).

Runs on the 8 simulated CPU devices conftest forces. Pins:
- hierarchical all-reduce / all-gather / reduce-scatter bit-identical
  to the flat fp32 collectives over a 2x4 mesh (integer-valued data,
  so fp32 sums are exact and bit-compare is meaningful);
- int8 quantized all-reduce inside the documented error bound and
  EXACT for constant inputs;
- the bucketing scheduler preserving gradient values vs unbucketed
  sync (in-graph hook and eager fused path);
- plan selection (flat fallback), config plumbing, microbench output.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed import collectives as cc
from paddle_tpu.distributed.collectives import (
    BucketedGradSync, CollectiveConfig, build_buckets, configure,
    int8_error_bound, plan_hierarchy)
from paddle_tpu.distributed.mesh import build_device_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 simulated devices")


@pytest.fixture(scope="module")
def mesh():
    return build_device_mesh({"dp": 2, "mp": 4})


def _idata(rs, shape, lo=-32, hi=32):
    # integer-valued fp32: sums are exact in any association order, so
    # flat-vs-hierarchical comparisons are BIT comparisons
    return rs.randint(lo, hi, size=shape).astype(np.float32)


class TestPlan:
    def test_auto_two_level(self, mesh):
        p = plan_hierarchy(("dp", "mp"), mesh)
        assert not p.flat
        assert p.outer == ("dp",) and p.inner == "mp"
        assert p.inner_size == 4 and p.total_size == 8

    def test_axis_order_normalized(self, mesh):
        # innermost mesh axis becomes the fast level regardless of the
        # order the caller wrote
        p = plan_hierarchy(("mp", "dp"), mesh)
        assert p.inner == "mp" and p.outer == ("dp",)

    def test_flat_fallback_single_axis(self, mesh):
        p = plan_hierarchy(("mp",), mesh)
        assert p.flat and p.total_size == 4

    def test_degree_one_axes_dropped(self):
        m = build_device_mesh({"dp": 1, "mp": 8})
        p = plan_hierarchy(("dp", "mp"), m)
        assert p.flat and p.axes == ("mp",) and p.total_size == 8

    def test_forced_flat(self, mesh):
        assert plan_hierarchy(("dp", "mp"), mesh, hierarchy="flat").flat

    def test_unknown_axis_raises(self, mesh):
        with pytest.raises(ValueError, match="not in mesh"):
            plan_hierarchy(("nope",), mesh)


class TestHierarchicalBitIdentity:
    @pytest.mark.parametrize("shape", [(64,), (37,), (8, 7)])
    def test_all_reduce(self, mesh, shape):
        # 37 elements: not divisible by inner_size=4 — exercises the
        # padding path
        rs = np.random.RandomState(0)
        x = _idata(rs, (8,) + shape)
        flat = np.asarray(cc.all_reduce(x, ("dp", "mp"), mesh,
                                        compress=None, hierarchy="flat"))
        hier = np.asarray(cc.all_reduce(x, ("dp", "mp"), mesh,
                                        compress=None, hierarchy="auto"))
        assert np.array_equal(flat, hier)
        np.testing.assert_array_equal(flat, x.sum(axis=0))

    def test_reduce_scatter_placement(self, mesh):
        # output row d is device d's chunk: the comparison pins chunk
        # ASSIGNMENT, not just the global sum
        rs = np.random.RandomState(1)
        x = _idata(rs, (8, 32))
        flat = np.asarray(cc.reduce_scatter(x, ("dp", "mp"), mesh,
                                            hierarchy="flat"))
        hier = np.asarray(cc.reduce_scatter(x, ("dp", "mp"), mesh,
                                            hierarchy="auto"))
        assert flat.shape == (8, 4)
        assert np.array_equal(flat, hier)
        total = x.sum(axis=0)
        for d in range(8):
            np.testing.assert_array_equal(flat[d], total[4 * d:4 * d + 4])

    def test_all_gather_order(self, mesh):
        rs = np.random.RandomState(2)
        x = _idata(rs, (8, 5))
        flat = np.asarray(cc.all_gather(x, ("dp", "mp"), mesh,
                                        hierarchy="flat"))
        hier = np.asarray(cc.all_gather(x, ("dp", "mp"), mesh,
                                        hierarchy="auto"))
        assert np.array_equal(flat, hier)
        np.testing.assert_array_equal(flat, x.reshape(-1))

    def test_reduce_scatter_indivisible_raises(self, mesh):
        with pytest.raises(ValueError, match="not divisible"):
            cc.reduce_scatter(np.zeros((8, 30), np.float32),
                              ("dp", "mp"), mesh)

    def test_wrong_leading_dim_raises(self, mesh):
        with pytest.raises(ValueError, match="dim 0"):
            cc.all_reduce(np.zeros((4, 8), np.float32), ("dp", "mp"),
                          mesh)

    def test_tensor_in_tensor_out(self, mesh):
        x = paddle.to_tensor(np.ones((8, 6), np.float32))
        out = cc.all_reduce(x, ("dp", "mp"), mesh, compress=None)
        assert isinstance(out, paddle.Tensor)
        np.testing.assert_array_equal(out.numpy(), np.full(6, 8.0))


class TestQuantizedAllReduce:
    @pytest.mark.parametrize("hierarchy", ["auto", "flat"])
    def test_within_documented_bound(self, mesh, hierarchy):
        rs = np.random.RandomState(3)
        x = (rs.randn(8, 3000).astype(np.float32)) * 5
        ref = np.asarray(cc.all_reduce(x, ("dp", "mp"), mesh,
                                       compress=None, hierarchy="flat"))
        q = np.asarray(cc.all_reduce(x, ("dp", "mp"), mesh,
                                     compress="int8",
                                     hierarchy=hierarchy))
        bound = float(int8_error_bound(
            np.abs(x).max(), 8, bucket_absmax_out=np.abs(ref).max()))
        err = np.abs(q - ref).max()
        assert err <= bound
        # and the bound is not vacuous: it's small vs the data scale
        assert bound < np.abs(ref).max()

    @pytest.mark.parametrize("hierarchy", ["auto", "flat"])
    def test_constant_input_exact(self, mesh, hierarchy):
        for v in (3.25, -0.875, 11.0):
            x = np.full((8, 1037), v, np.float32)
            out = np.asarray(cc.all_reduce(x, ("dp", "mp"), mesh,
                                           compress="int8",
                                           hierarchy=hierarchy))
            np.testing.assert_array_equal(out, np.full(1037, v * 8))

    def test_zero_buckets_exact(self, mesh):
        x = np.zeros((8, 64), np.float32)
        out = np.asarray(cc.all_reduce(x, ("dp", "mp"), mesh,
                                       compress="int8"))
        assert np.all(out == 0)

    def test_runtime_error_bound_in_graph(self, mesh):
        # quantized_all_reduce(return_error_bound=True) reports a bound
        # the measured error respects, from inside shard_map
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed.collectives.quantized import (
            quantized_all_reduce)
        plan = plan_hierarchy(("dp", "mp"), mesh)
        rs = np.random.RandomState(4)
        x = rs.randn(8, 777).astype(np.float32)

        def inner(xl):
            out, bound = quantized_all_reduce(
                jnp.squeeze(xl, 0), plan, return_error_bound=True)
            return out, bound
        out, bound = shard_map(
            inner, mesh=mesh, in_specs=(P(("dp", "mp")),),
            out_specs=(P(), P()), check_vma=False)(jnp.asarray(x))
        err = np.abs(np.asarray(out) - x.sum(axis=0)).max()
        assert err <= float(bound)

    def test_config_routes_compress(self, mesh):
        x = np.full((8, 512), 1.5, np.float32)
        with configure(compress="int8"):
            out = np.asarray(cc.all_reduce(x, ("dp", "mp"), mesh))
        np.testing.assert_array_equal(out, np.full(512, 12.0))


class TestBucketing:
    def test_build_buckets_size_targeted(self):
        sizes = [("a", 100), ("b", 100), ("c", 150), ("d", 10),
                 ("e", 1000)]
        # 4-byte elems, 800-byte target -> a+b (800) | c+d (640) | e
        assert build_buckets(sizes, bucket_bytes=800) == \
            [["a", "b"], ["c", "d"], ["e"]]

    def test_build_buckets_oversized_tensor_alone(self):
        assert build_buckets([("big", 10 ** 6), ("s", 1)],
                             bucket_bytes=1024) == [["big"], ["s"]]

    def test_in_graph_hook_preserves_values(self, mesh):
        # shard_map over dp: per-device grads differ; bucketed sync must
        # equal plain psum-mean exactly (fp32, integer-valued)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        rs = np.random.RandomState(5)
        shapes = {"w1": (4, 8), "b1": (8,), "w2": (8, 3), "b2": (3,)}
        stacked = {k: _idata(rs, (2,) + s) for k, s in shapes.items()}
        hook = BucketedGradSync(axes=("dp",), bucket_bytes=64,
                                compress=None, mesh=mesh)

        def inner(gs):
            local = {k: jnp.squeeze(v, 0) for k, v in gs.items()}
            synced = hook(local)
            ref = {k: jax.lax.pmean(v, "dp") for k, v in local.items()}
            return synced, ref
        specs = {k: P("dp") for k in shapes}
        synced, ref = shard_map(
            inner, mesh=mesh, in_specs=(specs,),
            out_specs=({k: P() for k in shapes},
                       {k: P() for k in shapes}),
            check_vma=False)(stacked)
        for k in shapes:
            assert np.array_equal(np.asarray(synced[k]),
                                  np.asarray(ref[k])), k
            assert synced[k].shape == shapes[k]

    def test_in_graph_hook_means_without_registered_mesh(self, mesh):
        # no mesh registered with the hook: the mean divisor must come
        # from the BOUND axes (regression: a flat total_size=1 plan
        # silently turned mean into sum)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        hook = BucketedGradSync(axes=("dp",), mesh=None)
        x = np.asarray([[2.0, 4.0], [6.0, 8.0]], np.float32)

        def inner(g):
            return hook({"w": jnp.squeeze(g, 0)})["w"]
        out = shard_map(inner, mesh=mesh, in_specs=(P("dp"),),
                        out_specs=P(), check_vma=False)(jnp.asarray(x))
        np.testing.assert_array_equal(np.asarray(out), [4.0, 6.0])

    def test_zero_size_grads_skipped(self, mesh):
        # a zero-size gradient must pass through untouched, not shift
        # bucket offsets or crash the fused reshape
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        hook = BucketedGradSync(axes=("dp",), bucket_bytes=1 << 10,
                                mesh=mesh)
        gs = {"empty": np.zeros((2, 0, 3), np.float32),
              "w": np.asarray([[1.0, 3.0], [5.0, 7.0]], np.float32)}

        def inner(d):
            local = {k: jnp.squeeze(v, 0) for k, v in d.items()}
            return hook(local)
        out = shard_map(inner, mesh=mesh,
                        in_specs=({k: P("dp") for k in gs},),
                        # jax 0.9 refuses a sharded spec on a zero-size
                        # output (XLA replicates it): take it replicated
                        out_specs={"empty": P(), "w": P()},
                        check_vma=False)(
            {k: jnp.asarray(v) for k, v in gs.items()})
        assert out["empty"].shape == (0, 3)
        np.testing.assert_array_equal(np.asarray(out["w"]), [3.0, 5.0])
        # eager path: zero-size grads are filtered, others preserved
        from paddle_tpu.distributed.collectives import (
            bucketed_allreduce_gradients)
        p1 = paddle.to_tensor(np.zeros((0, 3), np.float32))
        p1.grad = paddle.to_tensor(np.zeros((0, 3), np.float32))
        p2 = paddle.to_tensor(np.ones((2, 2), np.float32))
        p2.grad = paddle.to_tensor(np.full((2, 2), 4.0, np.float32))
        bucketed_allreduce_gradients([p1, p2], bucket_bytes=8)
        np.testing.assert_array_equal(p2.grad.numpy(),
                                      np.full((2, 2), 4.0))

    def test_error_bound_budget_falls_back_to_fp32(self, mesh):
        # error_bound configured: buckets whose runtime bound exceeds
        # it must ship the fp32 reduction (bound=0 -> always fp32,
        # bit-equal to pmean); a lax budget keeps the quantized result
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        rs = np.random.RandomState(9)
        g = (rs.randn(2, 600) * 3).astype(np.float32)

        def run(bound):
            with configure(compress="int8", error_bound=bound):
                hook = BucketedGradSync(axes=("dp",), mesh=mesh)

            def inner(v):
                local = jnp.squeeze(v, 0)
                return hook({"w": local})["w"], \
                    jax.lax.pmean(local, "dp")
            return shard_map(inner, mesh=mesh, in_specs=(P("dp"),),
                             out_specs=(P(), P()), check_vma=False)(
                jnp.asarray(g))
        out0, ref = run(0.0)
        np.testing.assert_array_equal(np.asarray(out0), np.asarray(ref))
        outq, ref = run(1e9)
        assert np.abs(np.asarray(outq) - np.asarray(ref)).max() > 0

    def test_partially_bound_axes_raise(self, mesh):
        # hook over ("dp","mp") inside a shard_map that only binds
        # "dp": neither silently skipping nor subset-syncing is safe
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        hook = BucketedGradSync(axes=("dp", "mp"), mesh=mesh)
        sub = Mesh(np.array(jax.devices()[:2]), ("dp",))

        def inner(g):
            return hook({"w": jnp.squeeze(g, 0)})["w"]
        with pytest.raises(ValueError, match="only .* bound"):
            shard_map(inner, mesh=sub, in_specs=(P("dp"),),
                      out_specs=P(), check_vma=False)(
                jnp.ones((2, 4), jnp.float32))

    def test_hook_noop_outside_shard_map(self, mesh):
        # under plain jit (GSPMD) the axes are unbound: hook must be
        # identity, never a double reduction
        hook = BucketedGradSync(axes=("dp",), mesh=mesh)
        g = {"w": jnp.arange(6, dtype=jnp.float32)}
        out = jax.jit(lambda d: hook(d))(g)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(g["w"]))
        out2 = hook(dict(g))          # eager
        np.testing.assert_array_equal(np.asarray(out2["w"]),
                                      np.asarray(g["w"]))

    def test_eager_bucketed_matches_unbucketed(self):
        # world size 1: both paths must leave grads exactly unchanged
        # while exercising the fuse/split bookkeeping
        from paddle_tpu import nn
        from paddle_tpu.distributed.fleet.utils import (
            fused_allreduce_gradients)
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(6, 5), nn.ReLU(), nn.Linear(5, 2))
        x = paddle.to_tensor(np.ones((3, 6), np.float32))
        (net(x) ** 2).mean().backward()
        before = {i: p.grad.numpy().copy()
                  for i, p in enumerate(net.parameters())
                  if p.grad is not None}
        fused_allreduce_gradients(list(net.parameters()),
                                  bucket_bytes=40)   # tiny: many buckets
        for i, p in enumerate(net.parameters()):
            if p.grad is not None:
                np.testing.assert_array_equal(p.grad.numpy(), before[i])

    def test_dataparallel_sync_and_no_sync(self):
        from paddle_tpu import nn
        from paddle_tpu.distributed import DataParallel
        paddle.seed(0)
        net = DataParallel(nn.Linear(4, 2), comm_buffer_size=1)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        (net(x) ** 2).mean().backward()
        g = net._layers.weight.grad.numpy().copy()
        with net.no_sync():
            net.sync_gradients()          # must be a no-op
        np.testing.assert_array_equal(net._layers.weight.grad.numpy(), g)
        net.sync_gradients()              # world 1: identity
        np.testing.assert_array_equal(net._layers.weight.grad.numpy(), g)

    def test_optimizer_hook_wiring_flag_off_and_on(self, mesh):
        from paddle_tpu import nn, optimizer
        from paddle_tpu.distributed.collectives import attach_grad_sync
        net = nn.Linear(4, 2)
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=net.parameters())
        assert attach_grad_sync(opt, axes=("dp",)) is None   # default off
        assert opt._grad_sync is None
        with configure(bucketed_grad_sync=True):
            hook = attach_grad_sync(opt, axes=("dp",))
        assert hook is opt._grad_sync
        assert isinstance(hook, BucketedGradSync)
        # flag back off: a re-attach clears the stale bucketed hook
        # (re-sharding must not keep syncing over the old axis) but
        # leaves a custom user hook alone
        assert attach_grad_sync(opt, axes=("mp",)) is None
        assert opt._grad_sync is None
        custom = lambda g: g                        # noqa: E731
        opt._grad_sync = custom
        attach_grad_sync(opt, axes=("dp",))
        assert opt._grad_sync is custom
        opt._grad_sync = hook
        # functional_update with the hook attached (axes unbound ->
        # identity) must produce the same step as without it
        x = paddle.to_tensor(np.ones((3, 4), np.float32))
        (net(x) ** 2).mean().backward()
        params = {n: p._value for n, p in
                  zip(opt._param_names, opt._param_list)}
        grads = {n: p.grad._value for n, p in
                 zip(opt._param_names, opt._param_list)
                 if p.grad is not None}
        state = opt.functional_state()
        new_p, _ = opt.functional_update(params, grads, state, 0.1)
        opt._grad_sync = None
        ref_p, _ = opt.functional_update(params, grads, state, 0.1)
        for n in new_p:
            np.testing.assert_array_equal(np.asarray(new_p[n]),
                                          np.asarray(ref_p[n]))

    def test_group_sharded_attaches_hook_behind_flag(self, mesh):
        from paddle_tpu import nn, optimizer
        from paddle_tpu.distributed.mesh import set_current_mesh
        from paddle_tpu.distributed.sharding import group_sharded_parallel
        set_current_mesh(mesh)
        try:
            net = nn.Linear(8, 4)
            opt = optimizer.SGD(learning_rate=0.1,
                                parameters=net.parameters())
            group_sharded_parallel(net, opt, "os")
            assert opt._grad_sync is None            # flag off: untouched
            with configure(bucketed_grad_sync=True):
                group_sharded_parallel(net, opt, "os")
            assert isinstance(opt._grad_sync, BucketedGradSync)
        finally:
            set_current_mesh(None)


class TestConfig:
    def test_validation(self):
        with pytest.raises(ValueError):
            CollectiveConfig(hierarchy="ring")
        with pytest.raises(ValueError):
            CollectiveConfig(compress="fp4")

    def test_configure_scoped(self):
        base = cc.collective_config().compress
        with configure(compress="int8"):
            assert cc.collective_config().compress == "int8"
        assert cc.collective_config().compress == base


class TestProfilerSpans:
    def test_record_event_emitted(self, mesh):
        from paddle_tpu import profiler
        prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                                 timer_only=True)
        with prof:
            cc.all_reduce(np.ones((8, 16), np.float32), ("dp", "mp"),
                          mesh, compress=None)
        ev = prof._drain_events()
        names = {e["name"] for e in ev}
        assert any(n.startswith("collectives::all_reduce") for n in names)


class TestBareShardMapErrorBound:
    """Regression (ROADMAP open item, PR 2 code): the runtime bound of
    quantized_all_reduce derived n from plan.total_size, which is 1 for
    a plan built with no registered mesh (bare shard_map) — the bound
    was understated ~n-fold, so BucketedGradSync's error_bound
    hard-guarantee mode could keep over-budget buckets. n must come
    from psum(1, axes) like bucketing.py's mean divisor."""

    def _bare_mesh(self):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:8]), ("r",))

    def _host_expected_bound(self, per_dev, bucket=512):
        # replicate the wire format on host: quantize each contribution,
        # fp32-accumulate the dequants, re-quantize the reduction — the
        # documented two-phase bound with the TRUE n=8
        from paddle_tpu.distributed.collectives.hierarchical import \
            pad_to_multiple
        from paddle_tpu.distributed.collectives.quantized import (
            _dequantize, _quantize, int8_error_bound)
        qs = [_quantize(pad_to_multiple(
            jnp.asarray(x).reshape(-1), bucket)[0], bucket)
            for x in per_dev]
        s_in = float(max(jnp.max(s) for _, s in qs))
        acc = sum(jnp.sum(_dequantize(q[None], s[None]), axis=0)
                  for q, s in qs)
        _, s_out = _quantize(acc.reshape(-1), bucket)
        n = len(per_dev)
        return (float(int8_error_bound(s_in, n,
                                       bucket_absmax_out=jnp.max(s_out))),
                float(int8_error_bound(s_in, 1,
                                       bucket_absmax_out=jnp.max(s_out))))

    def test_bound_counts_bound_ranks_not_plan_size(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed.collectives.hierarchical import \
            HierarchyPlan
        from paddle_tpu.distributed.collectives.quantized import \
            quantized_all_reduce
        mesh = self._bare_mesh()
        # EXACTLY what plan_hierarchy returns with no mesh registered:
        # flat, total_size=1 — the bug's trigger
        plan = HierarchyPlan(("r",), None, None, 1, 1)
        rs = np.random.RandomState(11)
        x = rs.randn(8, 777).astype(np.float32)

        def inner(xl):
            return quantized_all_reduce(jnp.squeeze(xl, 0), plan,
                                        return_error_bound=True)
        out, bound = shard_map(
            inner, mesh=mesh, in_specs=(P("r"),),
            out_specs=(P(), P()), check_vma=False)(jnp.asarray(x))
        err = np.abs(np.asarray(out) - x.sum(axis=0)).max()
        expected_n8, wrong_n1 = self._host_expected_bound(list(x))
        assert err <= float(bound)                 # contract holds
        np.testing.assert_allclose(float(bound), expected_n8,
                                   rtol=1e-6)      # n is REALLY 8
        assert float(bound) > 2 * wrong_n1         # not the n=1 bound

    def test_hard_guarantee_rejects_over_budget_under_bare_shard_map(
            self):
        # budget just under the true bound: with the fix the hook must
        # fall back to the exact fp32 reduction; pre-fix the ~8x
        # understated bound sat far below the budget and the quantized
        # (lossy) bucket was kept
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = self._bare_mesh()
        rs = np.random.RandomState(12)
        x = rs.randn(8, 777).astype(np.float32)
        expected_n8, wrong_n1 = self._host_expected_bound(list(x))
        budget = 0.9 * expected_n8
        assert budget > 2 * wrong_n1     # pre-fix bound passes budget
        hook = BucketedGradSync(axes=("r",), compress="int8", mesh=None)
        hook.error_bound = budget

        def inner(g):
            return hook({"w": jnp.squeeze(g, 0)})["w"]
        out = shard_map(inner, mesh=mesh, in_specs=(P("r"),),
                        out_specs=P(), check_vma=False)(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out),
                                   x.sum(axis=0) / 8, rtol=1e-6,
                                   atol=1e-6)      # exact fp32 fallback
