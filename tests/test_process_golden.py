"""Process-level golden parity (VERDICT r3 #5; SURVEY §4 takeaway:
multi-process single-host is how the reference tests multi-node).

Two REAL ``jax.distributed`` CPU processes (1 local device each, so the
global device count is 2 across OS processes — the integration seam the
8-fake-device dryrun cannot see) run the full pipeline:

  launch env contract -> init_parallel_env (jax.distributed.initialize)
  -> global 2-device Mesh build -> short DP train (eager backward +
  fused_allreduce_gradients, the reference Reducer pattern) -> sharded
  distributed checkpoint over the GLOBAL mesh (each process writes only
  its addressable shards)

then the DRIVER process (fresh single-process jax runtime, 1 device)
loads the checkpoint with reshard-on-load and must match a serial
golden run of the identical problem to float tolerance.
"""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

rank = int(os.environ["PADDLE_TRAINER_ID"])
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed.fleet.utils import fused_allreduce_gradients

dist.init_parallel_env()                    # jax.distributed.initialize
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2, jax.device_count()

# ---- mesh build over the GLOBAL device set ----
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(np.array(jax.devices()), ("dp",))

# ---- identical init on every rank ----
paddle.seed(0)
net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
net = dist.DataParallel(net)
opt = optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
mse = nn.MSELoss()

rs = np.random.RandomState(42)
X = rs.rand(16, 8).astype("float32")
Y = rs.rand(16, 2).astype("float32")
lo, hi = rank * 8, (rank + 1) * 8          # per-rank data shard

for step in range(5):
    x = paddle.to_tensor(X[lo:hi])
    y = paddle.to_tensor(Y[lo:hi])
    loss = mse(net(x), y)
    loss.backward()
    # reference Reducer pattern: mean-allreduce grads across dp ranks
    fused_allreduce_gradients(list(net.parameters()))
    opt.step()
    opt.clear_grad()

# ---- sharded distributed checkpoint over the global mesh ----
# place each param on the 2-device mesh (dim-0 sharded where divisible,
# replicated otherwise): each process then persists ONLY its
# addressable shard, and the single-process load must reassemble
state = {}
for name, p in net.state_dict().items():
    val = np.asarray(p._value if hasattr(p, "_value") else p)
    spec = P("dp") if val.ndim and val.shape[0] % 2 == 0 else P()
    sharding = NamedSharding(mesh, spec)
    garr = jax.make_array_from_callback(val.shape, sharding,
                                        lambda idx, v=val: v[idx])
    state[name] = garr
ckpt = os.environ["GOLDEN_CKPT_DIR"]
dist.save_state_dict(state, ckpt)
print("GOLDEN_OK", rank, float(loss.item()))
"""


@pytest.mark.slow
def test_two_process_dp_train_ckpt_reshard_matches_serial(tmp_path):
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ckpt = str(tmp_path / "golden_ckpt")
    env = dict(os.environ,
               PADDLE_TRAINERS_NUM="2",
               PADDLE_MASTER=f"127.0.0.1:{port}",
               GOLDEN_CKPT_DIR=ckpt,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_NUM_PROCESSES", None)
    procs = []
    for r in range(2):
        e = dict(env, PADDLE_TRAINER_ID=str(r))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER], env=e, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=420)
        outs.append(out.decode())
        assert p.returncode == 0, outs[-1]
        assert f"GOLDEN_OK {r}" in outs[-1], outs[-1]

    # ---- serial golden run in THIS process (single device) ----
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    opt = optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    mse = nn.MSELoss()
    rs = np.random.RandomState(42)
    X = rs.rand(16, 8).astype("float32")
    Y = rs.rand(16, 2).astype("float32")
    for step in range(5):
        x, y = paddle.to_tensor(X), paddle.to_tensor(Y)
        loss = mse(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
    serial = {k: np.asarray(v._value)
              for k, v in net.state_dict().items()}

    # ---- load-with-reshard into this single-process runtime ----
    import paddle_tpu.distributed as dist
    target = {k: paddle.to_tensor(np.zeros_like(v))
              for k, v in serial.items()}
    dist.load_state_dict(target, ckpt)
    assert set(target) == set(serial)
    for k in serial:
        # dist run: mean of two half-batch grads == full-batch grad of
        # the mean loss up to float reassociation
        np.testing.assert_allclose(
            np.asarray(target[k]._value), serial[k], rtol=1e-5,
            atol=1e-6, err_msg=f"param {k} diverged from serial golden")
