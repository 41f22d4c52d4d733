"""Eager collective + point-to-point communication API.

Reference parity: python/paddle/distributed/communication/ +
paddle/phi/core/distributed/ProcessGroup* (NCCL) — verify.

TPU-native design: the *perf path* never calls these eagerly — GSPMD emits
collectives inside jitted programs over the mesh (SURVEY §2.4). This module
provides the paddle-compatible eager API for host-level coordination:

- world-scoped collectives lower to jax multihost utilities (tiny XLA
  collective programs over DCN/ICI);
- subset ``Group`` collectives and all point-to-point ops (send/recv/
  isend/irecv/batch_isend_irecv) ride the C++ TCPStore key-value rendezvous
  (``paddle_tpu.core.native_api.TCPStore``) — the same transport the
  reference's gloo/TCPStore host path uses. They are host-bandwidth
  control-plane ops by design; bulk tensor exchange belongs inside jitted
  programs (shard_map ppermute / collective_permute).

Eager ``reduce_scatter``/``alltoall`` across processes are implemented via
allgather-then-slice: O(world) traffic, correctness-only — documented,
deliberate (the O(shard) path is the GSPMD one inside jit).
"""
from __future__ import annotations

import dataclasses
import io
import os
import pickle
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..tensor import Tensor
from ..utils.flags import env_float, env_int, env_str

__all__ = ["ReduceOp", "Group", "all_reduce", "all_gather",
           "all_gather_object", "reduce_scatter", "broadcast", "scatter",
           "reduce", "alltoall", "alltoall_single", "global_scatter",
           "global_gather", "send", "recv",
           "barrier", "new_group", "get_group", "destroy_process_group",
           "wait", "stream", "P2POp", "batch_isend_irecv", "isend", "irecv"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    def __init__(self, ranks, gid=0, name=None):
        self.ranks = list(ranks)
        self.id = gid
        self.name = name or f"group_{gid}"

    @property
    def nranks(self):
        return len(self.ranks)

    @property
    def world_size(self):
        return len(self.ranks)

    @property
    def rank(self):
        pid = _my_rank()
        return self.ranks.index(pid) if pid in self.ranks else -1

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def is_member(self):
        return _my_rank() in self.ranks

    def __repr__(self):
        return f"Group(id={self.id}, ranks={self.ranks})"


_GROUPS: dict[int, Group] = {}
_NEXT_GID = [1]


def _my_rank() -> int:
    return env_int("PADDLE_TRAINER_ID", jax.process_index())


def _world_size() -> int:
    return env_int("PADDLE_TRAINERS_NUM", jax.process_count())


def _world():
    if 0 not in _GROUPS:
        _GROUPS[0] = Group(list(range(_world_size())), 0, "world")
    return _GROUPS[0]


def new_group(ranks=None, backend=None, timeout=None):
    """Create a communication group over ``ranks``.

    Group ids are assigned from a process-local monotonically increasing
    counter; as in the reference, every rank must call ``new_group`` in the
    same order so ids agree across the job."""
    gid = _NEXT_GID[0]
    _NEXT_GID[0] += 1
    g = Group(sorted(ranks) if ranks is not None
              else list(range(_world_size())), gid)
    _GROUPS[gid] = g
    return g


def get_group(gid=0):
    return _GROUPS.get(gid, _world())


def destroy_process_group(group=None):
    global _STORE
    if group is not None and group.id in _GROUPS and group.id != 0:
        del _GROUPS[group.id]
        return
    _GROUPS.clear()
    with _STORE_LOCK:
        if _STORE is not None and hasattr(_STORE, "close"):
            try:
                _STORE.close()
            except Exception:
                pass
        _STORE = None
    # reset sequence counters so a re-initialized job starts in lock-step
    # with fresh peers (elastic restart path)
    with _SEQ_LOCK:
        _SEND_SEQ.clear()
        _RECV_SEQ.clear()
        _COLL_SEQ.clear()
    _NEXT_GID[0] = 1


def _val(t):
    return t._value if isinstance(t, Tensor) else jnp.asarray(t)


def _single_process() -> bool:
    return _world_size() == 1


def _is_world(group) -> bool:
    return group is None or group.id == 0 or \
        sorted(group.ranks) == list(range(_world_size()))


# --------------------------------------------------------------------------
# store transport (p2p + subset-group collectives)
# --------------------------------------------------------------------------

class _LocalStore:
    """In-process store with TCPStore semantics, used when world_size == 1
    (self-sends, and multi-"rank" tests driven from threads)."""

    def __init__(self):
        self._d: dict[str, bytes] = {}
        self._cv = threading.Condition()

    def set(self, key, value):
        if isinstance(value, str):
            value = value.encode()
        with self._cv:
            self._d[key] = bytes(value)
            self._cv.notify_all()

    def get(self, key):
        with self._cv:
            self._cv.wait_for(lambda: key in self._d, timeout=60)
            return self._d[key]

    def wait(self, key):
        with self._cv:
            if not self._cv.wait_for(lambda: key in self._d, timeout=60):
                raise TimeoutError(f"store wait timed out on {key!r}")

    def add(self, key, delta):
        with self._cv:
            cur = int.from_bytes(self._d.get(key, b"\0" * 8), "little",
                                 signed=True)
            cur += int(delta)
            self._d[key] = cur.to_bytes(8, "little", signed=True)
            self._cv.notify_all()
            return cur

    def check(self, key):
        with self._cv:
            return key in self._d

    def delete_key(self, key):
        with self._cv:
            self._d.pop(key, None)

    def close(self):
        pass


_STORE = None
_STORE_LOCK = threading.Lock()


def _get_store():
    """Lazily connect to the job's TCPStore (PADDLE_MASTER env from the
    launch contract — distributed/launch). Falls back to an in-process
    store for world_size == 1."""
    global _STORE
    with _STORE_LOCK:
        if _STORE is not None:
            return _STORE
        master = env_str("PADDLE_MASTER", "") or None
        if _single_process() or not master:
            if not _single_process():
                raise RuntimeError(
                    "point-to-point / subset-group eager comm needs the "
                    "TCPStore rendezvous: launch with paddle_tpu.distributed."
                    "launch (sets PADDLE_MASTER) or set PADDLE_MASTER="
                    "host:port")
            _STORE = _LocalStore()
            return _STORE
        from ..core.native_api import TCPStore
        host, port = master.rsplit(":", 1)
        _STORE = TCPStore(host, int(port), is_master=_my_rank() == 0,
                          world_size=_world_size())
        return _STORE


def _pack(arr) -> bytes:
    a = np.asarray(arr)
    buf = io.BytesIO()
    # npy format keeps dtype (incl. bfloat16 via jax's ml_dtypes) + shape
    if a.dtype == jnp.bfloat16:
        np.save(buf, a.view(np.uint16))
        return b"BF16" + buf.getvalue()
    np.save(buf, a)
    return b"RAW0" + buf.getvalue()


def _unpack(data: bytes):
    tag, body = data[:4], data[4:]
    a = np.load(io.BytesIO(body))
    if tag == b"BF16":
        a = a.view(jnp.bfloat16)
    return jnp.asarray(a)


# per-(src,dst) monotonically increasing sequence numbers so repeated
# sends/recvs between the same pair match deterministically
_SEND_SEQ: dict[tuple, int] = {}
_RECV_SEQ: dict[tuple, int] = {}
_SEQ_LOCK = threading.Lock()


class Task:
    """Async handle returned by isend/irecv (paddle task.wait() parity)."""

    def __init__(self, thread: Optional[threading.Thread] = None,
                 result_box: Optional[list] = None):
        self._thread = thread
        self._box = result_box

    def wait(self, timeout=None):
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise TimeoutError("p2p task did not complete")
            self._thread = None
        if self._box and isinstance(self._box[0], BaseException):
            raise self._box[0]
        return True

    def is_completed(self):
        return self._thread is None or not self._thread.is_alive()


def _reserve(seqs: dict, src: int, dst: int, group) -> str:
    """The store key of the next message ``src`` -> ``dst``. Taken in the
    CALLER's thread: ``isend(a); isend(b)`` (and ``batch_isend_irecv``)
    number their messages in call order, whichever worker thread runs
    first."""
    gid = group.id if group else 0
    with _SEQ_LOCK:
        seq = seqs.get((gid, src, dst), 0)
        seqs[(gid, src, dst)] = seq + 1
    return f"p2p/{gid}/{src}->{dst}/{seq}"


def _put(key: str, tensor):
    _warn_if_bulk(_val(tensor), "send")
    _get_store().set(key, _pack(_val(tensor)))


def _take(key: str, tensor):
    store = _get_store()
    store.wait(key)
    v = _unpack(store.get(key))
    store.delete_key(key)
    if isinstance(tensor, Tensor):
        tensor._update_value(v.astype(_val(tensor).dtype)
                             if v.dtype != _val(tensor).dtype else v)
        return tensor
    return Tensor(v)


def send(tensor, dst=0, group=None, sync_op=True):
    """Host-level point-to-point send over the TCPStore transport.
    ``dst`` is the GLOBAL rank (reference semantics, same convention as
    broadcast/scatter); ``group`` only namespaces the exchange."""
    _put(_reserve(_SEND_SEQ, _my_rank(), dst, group), tensor)


def recv(tensor, src=0, group=None, sync_op=True):
    """Blocking receive matching :func:`send` from GLOBAL rank ``src``."""
    return _take(_reserve(_RECV_SEQ, src, _my_rank(), group), tensor)


def _async(fn, *args, **kw):
    box = [None]

    def run():
        try:
            box[0] = fn(*args, **kw)
        except BaseException as e:  # surfaced in Task.wait
            box[0] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return Task(t, box)


def isend(tensor, dst=0, group=None):
    return _async(_put, _reserve(_SEND_SEQ, _my_rank(), dst, group), tensor)


def irecv(tensor, src=0, group=None):
    return _async(_take, _reserve(_RECV_SEQ, src, _my_rank(), group),
                  tensor)


@dataclasses.dataclass
class P2POp:
    op: object
    tensor: object
    peer: int
    group: object = None


def batch_isend_irecv(p2p_op_list):
    """Issue a batch of P2POps concurrently; returns list of Tasks.

    Sends are issued first (store sets never block), then receives — the
    standard deadlock-free ordering for symmetric exchange patterns."""
    for p in p2p_op_list:
        if p.op not in (send, isend, recv, irecv):
            raise ValueError(
                f"P2POp.op must be send/isend/recv/irecv, got {p.op}")
    tasks = []
    for p in p2p_op_list:
        if p.op in (send, isend):
            tasks.append(isend(p.tensor, p.peer, p.group))
    for p in p2p_op_list:
        if p.op in (recv, irecv):
            tasks.append(irecv(p.tensor, p.peer, p.group))
    return tasks


# --------------------------------------------------------------------------
# store-based subset-group collectives
# --------------------------------------------------------------------------

_COLL_SEQ: dict[tuple, int] = {}


def _coll_round(group, op_name, me) -> int:
    # keyed per member rank: counters advance in lock-step across members
    # whether they live in separate processes or threads of one process
    with _SEQ_LOCK:
        k = (group.id, op_name, me)
        seq = _COLL_SEQ.get(k, 0)
        _COLL_SEQ[k] = seq + 1
        return seq


_BULK_WARNED_OPS: set = set()


def _warn_if_bulk(value, op_name):
    """Size guard for the store transport (VERDICT r4 next #9).

    The store path is a CONTROL-PLANE transport (pickle over the TCP
    store, O(world) per member) — bulk tensor exchange belongs inside
    jit where XLA collectives ride ICI. Configurable:

    - ``PT_EAGER_COLLECTIVE_WARN_MB`` (default 1): threshold in MB.
    - ``PT_EAGER_COLLECTIVE_GUARD``: ``warn`` (default, once per op
      name), ``error`` (raise RuntimeError), or ``off``.
    """
    mode = env_str("PT_EAGER_COLLECTIVE_GUARD", "warn")
    if mode == "off":
        return
    try:
        nbytes = int(np.asarray(value).nbytes)
    except Exception:
        return
    try:
        limit_mb = env_float("PT_EAGER_COLLECTIVE_WARN_MB", 1.0)
    except ValueError:      # guard path: malformed knob must not raise
        limit_mb = 1.0
    if nbytes <= limit_mb * 1e6:
        return
    msg = (f"eager {op_name} of {nbytes / 1e6:.1f} MB rides the host "
           "TCP store (control-plane transport, O(world) per member); "
           "for bulk data use collectives inside jit/shard_map where "
           "XLA lowers them to ICI. Set PT_EAGER_COLLECTIVE_GUARD="
           "error to raise, =off to silence, or "
           "PT_EAGER_COLLECTIVE_WARN_MB to tune the threshold")
    if mode == "error":
        raise RuntimeError(msg)
    if op_name not in _BULK_WARNED_OPS:
        _BULK_WARNED_OPS.add(op_name)
        import warnings
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _store_gather(value, group, op_name):
    """All group members contribute `value`; returns the list of all
    members' values ordered by group.ranks. Last reader cleans up."""
    _warn_if_bulk(value, op_name)
    store = _get_store()
    me = group.rank
    rnd = _coll_round(group, op_name, me)
    if me < 0:
        raise RuntimeError(
            f"rank {_my_rank()} called {op_name} on {group} it is not a "
            f"member of")
    base = f"coll/{group.id}/{op_name}/{rnd}"
    store.set(f"{base}/{me}", _pack(value))
    outs = []
    for r in range(group.nranks):
        key = f"{base}/{r}"
        store.wait(key)
        outs.append(_unpack(store.get(key)))
    done = store.add(f"{base}/done", 1)
    if done == group.nranks:
        for r in range(group.nranks):
            store.delete_key(f"{base}/{r}")
        store.delete_key(f"{base}/done")
    return outs


def _reduce_terms(op, parts):
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        out = sum(parts[1:], parts[0])
        return out / len(parts) if op == ReduceOp.AVG else out
    if op == ReduceOp.MAX:
        return jax.tree.reduce(jnp.maximum, parts)
    if op == ReduceOp.MIN:
        return jax.tree.reduce(jnp.minimum, parts)
    out = parts[0]
    for p in parts[1:]:
        out = out * p
    return out


def _use_multihost(group) -> bool:
    """Multihost fast path is valid only when the group is the world AND
    jax itself was initialized multi-process (jax.distributed). On
    TCPStore-only jobs (each worker a 1-process jax runtime) world
    collectives must ride the store too."""
    return _is_world(group) and jax.process_count() == _world_size()


def _gather_all(v, group, op_name):
    """Gather `v` from every member of `group`, ordered by group rank.

    World groups take the multihost fast path when jax is multi-process;
    everything else rides the store so non-members need not participate."""
    if _single_process() and _is_world(group):
        return [v]
    if _use_multihost(group):
        from jax.experimental import multihost_utils
        g = multihost_utils.process_allgather(v)
        return [jnp.asarray(g[i]) for i in range(_world_size())]
    return _store_gather(v, group or _world(), op_name)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    v = _val(tensor)
    parts = _gather_all(v, group, f"allreduce_{op}")
    if len(parts) == 1:
        return tensor
    out = _reduce_terms(op, parts)
    tensor._update_value(out.astype(v.dtype))
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    parts = _gather_all(_val(tensor), group, "allgather")
    tensor_list.extend(Tensor(p) for p in parts)
    return tensor_list


def all_gather_object(object_list, obj, group=None):
    if _single_process() and _is_world(group):
        object_list.append(obj)
        return object_list
    data = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    if _use_multihost(group):
        from jax.experimental import multihost_utils
        n = np.array([data.size], np.int32)
        sizes = multihost_utils.process_allgather(jnp.asarray(n))
        maxn = int(np.max(sizes))
        padded = np.zeros(maxn, np.uint8)
        padded[:data.size] = data
        rows = multihost_utils.process_allgather(jnp.asarray(padded))
        for row, size in zip(rows, np.asarray(sizes).reshape(-1)):
            object_list.append(
                pickle.loads(bytes(np.asarray(row)[:int(size)])))
        return object_list
    rows = _store_gather(data, group or _world(), "allgather_obj")
    object_list.extend(pickle.loads(bytes(np.asarray(r))) for r in rows)
    return object_list


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    g = group or _world()
    if _single_process() and _is_world(group):
        tensor._update_value(_val(tensor_list[0]))
        return tensor
    stacked = jnp.stack([_val(t) for t in tensor_list])
    parts = _gather_all(stacked, g, f"reducescatter_{op}")
    total = _reduce_terms(op, parts)
    me = g.rank if not _is_world(g) else _my_rank()
    tensor._update_value(total[me])
    return tensor


def broadcast(tensor, src=0, group=None, sync_op=True):
    if _single_process() and _is_world(group):
        return tensor
    v = _val(tensor)
    if _use_multihost(group):
        from jax.experimental import multihost_utils
        out = multihost_utils.broadcast_one_to_all(
            v, is_source=_my_rank() == src)
        tensor._update_value(jnp.asarray(out))
        return tensor
    # store path: src is the GLOBAL rank (reference semantics)
    g = group or _world()
    parts = _store_gather(v, g, "broadcast")
    idx = g.get_group_rank(src)
    if idx < 0:
        raise ValueError(f"broadcast src={src} is not a member of {g}")
    tensor._update_value(parts[idx].astype(v.dtype))
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    all_reduce(tensor, op, group, sync_op)  # reduce-to-all ⊇ reduce
    return tensor


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """Collect ``tensor`` from every rank into ``gather_list`` on rank
    ``dst`` (reference: paddle.distributed.gather — verify). Other
    ranks leave ``gather_list`` untouched. Control-plane transport like
    the other eager collectives; bulk data belongs inside jitted
    programs."""
    g = group or _world()
    if _single_process() and _is_world(group):
        if gather_list is not None:
            gather_list.append(Tensor(_val(tensor)))
        return gather_list
    parts = _store_gather(_val(tensor), g, "gather")
    idx = g.get_group_rank(dst)
    if idx < 0:
        raise ValueError(f"gather dst={dst} is not a member of {g}")
    me = g.rank if not _is_world(g) else _my_rank()
    if me == idx and gather_list is not None:
        gather_list.extend(Tensor(jnp.asarray(p)) for p in parts)
    return gather_list


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g = group or _world()
    if _single_process() and _is_world(group):
        if tensor_list:
            tensor._update_value(_val(tensor_list[0]))
        return tensor
    stacked = jnp.stack([_val(t) for t in tensor_list]) if tensor_list \
        else jnp.zeros((g.nranks,) + tuple(tensor.shape), tensor.dtype)
    if _use_multihost(group):
        from jax.experimental import multihost_utils
        v = multihost_utils.broadcast_one_to_all(
            stacked, is_source=_my_rank() == src)
        tensor._update_value(jnp.asarray(v)[_my_rank()])
        return tensor
    parts = _store_gather(stacked, g, "scatter")
    idx = g.get_group_rank(src)
    if idx < 0:
        raise ValueError(f"scatter src={src} is not a member of {g}")
    tensor._update_value(parts[idx][g.rank])
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    g = group or _world()
    if out_tensor_list is None:
        out_tensor_list = []
    if _single_process() and _is_world(group):
        out_tensor_list.extend(Tensor(_val(t)) for t in in_tensor_list)
        return out_tensor_list
    stacked = jnp.stack([_val(t) for t in in_tensor_list])
    rows = _gather_all(stacked, g, "alltoall")  # [nranks](nranks, ...)
    me = g.rank if not _is_world(g) else _my_rank()
    for p in range(len(rows)):
        out_tensor_list.append(Tensor(jnp.asarray(rows[p][me])))
    return out_tensor_list


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    g = group or _world()
    n = 1 if (_single_process() and _is_world(group)) else g.nranks
    parts = jnp.split(_val(in_tensor), n)
    outs = alltoall([Tensor(p) for p in parts], group=group)
    res = jnp.concatenate([_val(t) for t in outs])
    if out_tensor is not None:
        out_tensor._update_value(res)
        return out_tensor
    return Tensor(res)


def global_scatter(x, local_count, global_count, group=None, sync_op=True):
    """MoE expert exchange (reference parity: paddle.distributed.utils.
    global_scatter / paddle/fluid/operators/collective/global_scatter_op.*
    — verify).

    ``x`` rows are grouped by GLOBAL expert id with ``local_count[i]``
    rows destined for expert ``i`` (experts are owned round-robin-block:
    rank r owns experts [r*e_per, (r+1)*e_per), e_per = E/nranks). Each
    rank receives the rows for ITS experts from every rank, ordered
    (local_expert, src_rank) — the reference's layout.

    Eager control-plane shim over the object-exchange path (variable row
    counts per destination make this a ragged alltoall). The COMPILED
    hot path is MoELayer's dual-map gather dispatch, where GSPMD inserts
    the equivalent all-to-all over the "ep" mesh axis — use that for
    training steps; this API exists for reference-parity orchestration
    and tests."""
    import numpy as np
    g = group or _world()
    lc = [int(v) for v in np.asarray(_val(local_count)).reshape(-1)]
    xv = np.asarray(_val(x))
    nranks = 1 if (_single_process() and _is_world(group)) else g.nranks
    if len(lc) % nranks:
        raise ValueError(
            f"local_count length {len(lc)} not divisible by world size "
            f"{nranks}")
    e_per = len(lc) // nranks
    # split x into per-global-expert blocks
    offs = np.cumsum([0] + lc)
    if offs[-1] != xv.shape[0]:
        raise ValueError(
            f"sum(local_count)={offs[-1]} != rows of x {xv.shape[0]}")
    blocks = [xv[offs[i]:offs[i + 1]] for i in range(len(lc))]
    if nranks == 1:
        return Tensor(jnp.asarray(np.concatenate(blocks)
                                  if blocks else xv))
    gathered = []
    all_gather_object(gathered, blocks, group=g)
    me = g.rank if not _is_world(g) else _my_rank()
    out = []
    for i_local in range(e_per):
        for r in range(nranks):
            out.append(gathered[r][me * e_per + i_local])
    res = np.concatenate(out) if out else xv[:0]
    return Tensor(jnp.asarray(res))


def global_gather(x, local_count, global_count, group=None, sync_op=True):
    """Inverse of :func:`global_scatter` (reference parity:
    global_gather_op.* — verify): rows grouped (local_expert, src_rank)
    with ``global_count[i_local*nranks + r]`` rows from rank ``r`` are
    returned to their source ranks, restoring the sender's
    global-expert-id grouping described by ``local_count``."""
    import numpy as np
    g = group or _world()
    gc = [int(v) for v in np.asarray(_val(global_count)).reshape(-1)]
    lc = [int(v) for v in np.asarray(_val(local_count)).reshape(-1)]
    xv = np.asarray(_val(x))
    nranks = 1 if (_single_process() and _is_world(group)) else g.nranks
    if len(lc) != len(gc):
        raise ValueError(
            f"local_count length {len(lc)} != global_count length "
            f"{len(gc)} (both must cover all E experts)")
    if len(gc) % nranks:
        raise ValueError(
            f"global_count length {len(gc)} not divisible by world size "
            f"{nranks}")
    e_per = len(gc) // nranks
    offs = np.cumsum([0] + gc)
    if offs[-1] != xv.shape[0]:
        raise ValueError(
            f"sum(global_count)={offs[-1]} != rows of x {xv.shape[0]}")
    # block (i_local, r) = rows received from rank r for my expert i_local
    blocks = [xv[offs[i]:offs[i + 1]] for i in range(len(gc))]
    if nranks == 1:
        return Tensor(jnp.asarray(np.concatenate(blocks)
                                  if blocks else xv))
    gathered = []
    all_gather_object(gathered, blocks, group=g)
    me = g.rank if not _is_world(g) else _my_rank()
    # my original send order: for each global expert i (owner o, slot
    # i_local), my block sits at position (i_local, me) in o's buffer
    out = []
    for i in range(len(lc)):
        owner, i_local = divmod(i, e_per)
        out.append(gathered[owner][i_local * nranks + me])
    res = np.concatenate(out) if out else xv[:0]
    return Tensor(jnp.asarray(res))


def barrier(group=None):
    if _single_process() and _is_world(group):
        return
    if _use_multihost(group):
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_barrier")
        return
    _store_gather(jnp.zeros((), jnp.int32), group or _world(), "barrier")


def wait(tensor, group=None, use_calc_stream=True):
    v = _val(tensor)
    if hasattr(v, "block_until_ready"):
        v.block_until_ready()


class stream:
    """paddle.distributed.stream.* namespace: same ops, async handles."""
    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce_scatter = staticmethod(reduce_scatter)
    broadcast = staticmethod(broadcast)
    alltoall = staticmethod(alltoall)
    send = staticmethod(send)
    recv = staticmethod(recv)
