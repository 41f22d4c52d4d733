"""Eager collective/p2p API tests.

Single-process: world collectives are identity; p2p + subset-group
collectives ride the in-process store (threads emulate group members).
Multi-process: two spawned workers exchange tensors over the real
TCPStore rendezvous (PADDLE_MASTER contract)."""
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import communication as comm


def t(x):
    return paddle.to_tensor(np.asarray(x, np.float32))


class _FakeGroup(comm.Group):
    """Group whose local rank is pinned (thread-emulated members)."""

    def __init__(self, ranks, gid, my_rank):
        super().__init__(ranks, gid)
        self._my = my_rank

    @property
    def rank(self):
        return self._my


def test_world_collectives_single_process_identity():
    x = t([1.0, 2.0])
    assert np.allclose(dist.all_reduce(x).numpy(), [1.0, 2.0])
    outs = []
    dist.all_gather(outs, t([3.0]))
    assert len(outs) == 1 and float(outs[0].numpy()[0]) == 3.0
    objs = []
    dist.all_gather_object(objs, {"a": 1})
    assert objs == [{"a": 1}]
    dist.barrier()


def test_send_recv_self():
    src = t([1.0, 2.0, 3.0])
    dst = t([0.0, 0.0, 0.0])
    dist.send(src, dst=0)
    dist.recv(dst, src=0)
    assert np.allclose(dst.numpy(), [1, 2, 3])


def test_isend_irecv_tasks():
    dst = t([0.0, 0.0])
    task_r = dist.irecv(dst, src=0)
    task_s = dist.isend(t([5.0, 6.0]), dst=0)
    task_s.wait()
    task_r.wait()
    assert np.allclose(dst.numpy(), [5, 6])


def test_batch_isend_irecv():
    recv_buf = t([0.0])
    ops = [comm.P2POp(comm.isend, t([9.0]), 0),
           comm.P2POp(comm.irecv, recv_buf, 0)]
    for task in dist.batch_isend_irecv(ops):
        task.wait()
    assert float(recv_buf.numpy()[0]) == 9.0


def test_batch_isend_irecv_rejects_bad_op():
    with pytest.raises(ValueError):
        dist.batch_isend_irecv([comm.P2POp(print, t([1.0]), 0)])


def test_send_recv_seq_ordering():
    # two sends then two recvs: FIFO per (src,dst) pair
    dist.send(t([1.0]), dst=0)
    dist.send(t([2.0]), dst=0)
    a, b = t([0.0]), t([0.0])
    dist.recv(a, src=0)
    dist.recv(b, src=0)
    assert float(a.numpy()[0]) == 1.0 and float(b.numpy()[0]) == 2.0


def _run_group_members(fn, nranks=2, gid=99):
    """Run fn(group_for_rank_r, results, r) on a thread per member."""
    results = [None] * nranks
    errs = []

    def worker(r):
        try:
            g = _FakeGroup(list(range(nranks)), gid, r)
            fn(g, results, r)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(nranks)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30)
    assert not errs, errs
    return results


def test_group_allreduce_threads():
    def body(g, results, r):
        x = t([float(r + 1), 10.0 * (r + 1)])
        comm.all_reduce(x, group=g)
        results[r] = x.numpy()

    results = _run_group_members(body, gid=101)
    for res in results:
        assert np.allclose(res, [3.0, 30.0])  # 1+2, 10+20


def test_group_allgather_threads():
    def body(g, results, r):
        outs = []
        comm.all_gather(outs, t([float(r)]), group=g)
        results[r] = [float(o.numpy()[0]) for o in outs]

    results = _run_group_members(body, gid=102)
    assert results[0] == [0.0, 1.0] and results[1] == [0.0, 1.0]


def test_group_broadcast_threads():
    def body(g, results, r):
        x = t([float(r * 7 + 1)])
        comm.broadcast(x, src=1, group=g)
        results[r] = float(x.numpy()[0])

    results = _run_group_members(body, gid=103)
    assert results == [8.0, 8.0]  # rank1's value 1*7+1


def test_group_reduce_scatter_threads():
    def body(g, results, r):
        out = t([0.0])
        comm.reduce_scatter(out, [t([float(r + 1)]), t([float(10 * (r + 1))])],
                            group=g)
        results[r] = float(out.numpy()[0])

    results = _run_group_members(body, gid=104)
    assert results == [3.0, 30.0]


def test_group_alltoall_threads():
    def body(g, results, r):
        outs = comm.alltoall([t([float(10 * r)]), t([float(10 * r + 1)])],
                             group=g)
        results[r] = [float(o.numpy()[0]) for o in outs]

    results = _run_group_members(body, gid=105)
    assert results[0] == [0.0, 10.0] and results[1] == [1.0, 11.0]


def test_group_scatter_threads():
    def body(g, results, r):
        out = t([0.0])
        comm.scatter(out, [t([100.0]), t([200.0])], src=0, group=g)
        results[r] = float(out.numpy()[0])

    results = _run_group_members(body, gid=106)
    assert results == [100.0, 200.0]


def test_group_barrier_threads():
    def body(g, results, r):
        comm.barrier(group=g)
        results[r] = True

    assert _run_group_members(body, gid=107) == [True, True]


_WORKER = r"""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
rank = int(os.environ["PADDLE_TRAINER_ID"])
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
x = paddle.to_tensor(np.asarray([float(rank + 1)] * 4, np.float32))
if rank == 0:
    dist.send(x, dst=1)
    buf = paddle.to_tensor(np.zeros(4, np.float32))
    dist.recv(buf, src=1)
    assert np.allclose(buf.numpy(), 2.0), buf.numpy()
else:
    buf = paddle.to_tensor(np.zeros(4, np.float32))
    dist.recv(buf, src=0)
    assert np.allclose(buf.numpy(), 1.0), buf.numpy()
    dist.send(x, dst=0)
print("P2P_OK", rank)
"""


@pytest.mark.slow
def test_p2p_two_processes(tmp_path, unused_tcp_port_factory=None):
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ,
               PADDLE_TRAINERS_NUM="2",
               PADDLE_MASTER=f"127.0.0.1:{port}",
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    procs = []
    for r in range(2):
        e = dict(env, PADDLE_TRAINER_ID=str(r))
        procs.append(subprocess.Popen([sys.executable, "-c", _WORKER],
                                      env=e, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    for r, p in enumerate(procs):
        # generous: two cold jax-on-CPU interpreter startups on a loaded
        # single-core host have been observed to near the old 120s
        out, _ = p.communicate(timeout=300)
        assert p.returncode == 0, out.decode()
        assert f"P2P_OK {r}".encode() in out


def test_batch_isend_irecv_multiple_sends():
    # regression: membership check must not trigger P2POp __eq__ on Tensors
    a, b = t([0.0, 0.0]), t([0.0, 0.0])
    ops = [comm.P2POp(comm.isend, t([1.0, 2.0]), 0),
           comm.P2POp(comm.isend, t([3.0, 4.0]), 0),
           comm.P2POp(comm.irecv, a, 0),
           comm.P2POp(comm.irecv, b, 0)]
    for task in dist.batch_isend_irecv(ops):
        task.wait()
    assert np.allclose(a.numpy(), [1, 2]) and np.allclose(b.numpy(), [3, 4])


def test_group_broadcast_global_src_and_invalid():
    def body(g, results, r):
        x = t([float(r + 1)])
        comm.broadcast(x, src=0, group=g)
        results[r] = float(x.numpy()[0])

    assert _run_group_members(body, gid=110) == [1.0, 1.0]

    def bad(g, results, r):
        try:
            comm.broadcast(t([1.0]), src=7, group=g)
        except ValueError:
            results[r] = "raised"

    assert _run_group_members(bad, gid=111) == ["raised", "raised"]


class TestFusedAllreduceGradients:
    def test_single_process_mean_noop(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed.fleet.utils import (
            fused_allreduce_gradients)
        paddle.seed(0)
        net = nn.Linear(4, 2)
        x = paddle.to_tensor(np.ones((3, 4), "float32"))
        (net(x) ** 2).mean().backward()
        before = net.weight.grad.numpy().copy()
        fused_allreduce_gradients(list(net.parameters()))
        # world size 1: mean over one rank == identity
        np.testing.assert_allclose(net.weight.grad.numpy(), before,
                                   rtol=1e-6)

    def test_skips_gradless_params(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed.fleet.utils import (
            fused_allreduce_gradients)
        net = nn.Linear(4, 2)
        fused_allreduce_gradients(list(net.parameters()))  # no grads: ok
        assert net.weight.grad is None


def test_global_scatter_gather_threads():
    """MoE expert exchange shims (reference global_scatter/global_gather
    ops): 2 ranks x 4 experts (2 per rank) with ragged per-expert row
    counts — verifies the (local_expert, src_rank) receive layout and
    the exact round trip through global_gather."""
    e_per = 2

    def make(r):
        lc = [1, 0, 2, 1] if r == 0 else [2, 1, 0, 1]
        rows = []
        for i, c in enumerate(lc):
            for j in range(c):
                rows.append([r * 100 + i * 10 + j])
        return np.asarray(rows, np.float32), lc

    lcs = {r: make(r) for r in (0, 1)}

    def body(g, results, r):
        x, lc = lcs[r]
        gc = [lcs[src][1][r * e_per + i_local]
              for i_local in range(e_per) for src in (0, 1)]
        y = comm.global_scatter(paddle.to_tensor(x), lc, gc, group=g)
        back = comm.global_gather(y, lc, gc, group=g)
        results[r] = (y.numpy(), back.numpy())

    results = _run_group_members(body, gid=120)
    for r in (0, 1):
        np.testing.assert_array_equal(results[r][1], lcs[r][0])
    # rank0 owns experts {0,1}: e0 <- r0:[0], r1:[100,101]; e1 <- r1:[110]
    np.testing.assert_array_equal(
        results[0][0].reshape(-1), [0.0, 100.0, 101.0, 110.0])
    # rank1 owns experts {2,3}: e2 <- r0:[20,21]; e3 <- r0:[30], r1:[130]
    np.testing.assert_array_equal(
        results[1][0].reshape(-1), [20.0, 21.0, 30.0, 130.0])


def test_global_scatter_single_process_world():
    x = paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(3, 2))
    y = comm.global_scatter(x, [1, 2], [1, 2])
    np.testing.assert_array_equal(y.numpy(), x.numpy())
    z = comm.global_gather(y, [1, 2], [1, 2])
    np.testing.assert_array_equal(z.numpy(), x.numpy())


def test_global_scatter_count_mismatch_raises():
    x = paddle.to_tensor(np.zeros((3, 2), np.float32))
    with pytest.raises(ValueError):
        comm.global_scatter(x, [1, 1], [1, 1])  # sum != rows


class TestBulkSizeGuard:
    """VERDICT r4 next #9: configurable size guard on the store
    transport — warn once per op / raise / off."""

    def test_warn_once_per_op(self, monkeypatch):
        import warnings
        monkeypatch.setenv("PT_EAGER_COLLECTIVE_WARN_MB", "0.001")
        monkeypatch.setattr(comm, "_BULK_WARNED_OPS", set())
        big = np.zeros(4096, np.float32)          # 16 KB > 1 KB
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            comm._warn_if_bulk(big, "allgather")
            comm._warn_if_bulk(big, "allgather")   # same op: no re-warn
            comm._warn_if_bulk(big, "scatter")     # new op: warns
        msgs = [x for x in w if "TCP store" in str(x.message)]
        assert len(msgs) == 2
        assert "jit/shard_map" in str(msgs[0].message)

    def test_error_mode_raises(self, monkeypatch):
        monkeypatch.setenv("PT_EAGER_COLLECTIVE_GUARD", "error")
        monkeypatch.setenv("PT_EAGER_COLLECTIVE_WARN_MB", "0.001")
        with pytest.raises(RuntimeError, match="TCP store"):
            comm._warn_if_bulk(np.zeros(4096, np.float32), "alltoall")

    def test_off_and_threshold(self, monkeypatch):
        import warnings
        monkeypatch.setattr(comm, "_BULK_WARNED_OPS", set())
        monkeypatch.setenv("PT_EAGER_COLLECTIVE_GUARD", "off")
        monkeypatch.setenv("PT_EAGER_COLLECTIVE_WARN_MB", "0.001")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            comm._warn_if_bulk(np.zeros(4096, np.float32), "gather")
        assert not [x for x in w if "TCP store" in str(x.message)]
        monkeypatch.setenv("PT_EAGER_COLLECTIVE_GUARD", "warn")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            comm._warn_if_bulk(np.zeros(8, np.float32), "gather")  # tiny
        assert not [x for x in w if "TCP store" in str(x.message)]
