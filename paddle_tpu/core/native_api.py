"""ctypes façades over libptcore with pure-Python fallbacks.

``TCPStore`` mirrors the reference's paddle/phi/core/distributed/store
API (set/get/add/wait/barrier over a rank0-hosted server — verify);
``ShmQueue`` is the DataLoader shared-memory transport.
"""
from __future__ import annotations

import ctypes
import os
import pickle
import socket
import socketserver
import threading
import time
from typing import Optional

from . import load_native


# ---------------------------------------------------------------------------
# TCPStore
# ---------------------------------------------------------------------------

class _PyStoreServer:
    """Fallback threaded KV server speaking pickle frames."""

    def __init__(self, port):
        kv, cv = {}, threading.Condition()

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                while True:
                    try:
                        head = self.rfile.read(4)
                        if len(head) < 4:
                            return
                        n = int.from_bytes(head, "little")
                        op, key, val = pickle.loads(self.rfile.read(n))
                    except (EOFError, ConnectionError, OSError):
                        return
                    if op == "set":
                        with cv:
                            kv[key] = val
                            cv.notify_all()
                        resp = b"ok"
                    elif op in ("get", "wait"):
                        with cv:
                            cv.wait_for(lambda: key in kv)
                            resp = kv[key] if op == "get" else b"ok"
                    elif op == "add":
                        with cv:
                            cur = int.from_bytes(kv.get(key, b"\0" * 8),
                                                 "little", signed=True)
                            cur += val
                            kv[key] = cur.to_bytes(8, "little", signed=True)
                            cv.notify_all()
                            resp = kv[key]
                    elif op == "check":
                        with cv:
                            resp = b"\1" if key in kv else b"\0"
                    elif op == "delete":
                        with cv:
                            kv.pop(key, None)
                        resp = b"ok"
                    else:
                        return
                    out = pickle.dumps(resp)
                    try:
                        self.wfile.write(len(out).to_bytes(4, "little")
                                         + out)
                    except (ConnectionError, OSError):
                        return

        socketserver.ThreadingTCPServer.allow_reuse_address = True
        self.server = socketserver.ThreadingTCPServer(("0.0.0.0", port),
                                                      Handler)
        self.server.daemon_threads = True
        self.port = self.server.server_address[1]
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()

    def stop(self):
        self.server.shutdown()
        self.server.server_close()


class MasterDaemon:
    """The rank0-hosted store server (reference: detail::MasterDaemon in
    tcp_store — verify). Start once; clients are TCPStore instances."""

    def __init__(self, port: int = 0):
        lib = load_native()
        self._native = None
        self._py = None
        if lib is not None:
            self._native = lib.pt_store_server_start(port)
            if self._native is None:
                raise OSError(f"cannot bind store server on port {port}")
            self.port = int(lib.pt_store_server_port(self._native))
        else:
            self._py = _PyStoreServer(port)
            self.port = self._py.port

    def stop(self):
        if self._native is not None:
            load_native().pt_store_server_stop(self._native)
            self._native = None
        if self._py is not None:
            self._py.stop()
            self._py = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


class TCPStore:
    """Client to a MasterDaemon (API parity: paddle.distributed's TCPStore
    / torch-style c10d store: set/get/add/wait/barrier)."""

    def __init__(self, host: str, port: int, is_master: bool = False,
                 world_size: int = 1, timeout: float = 60.0):
        self.world_size = world_size
        self._daemon = None
        if is_master:
            self._daemon = MasterDaemon(port)
            port = self._daemon.port
        self.host, self.port = host, port
        lib = load_native()
        self._lib = lib
        self._h = None
        self._sock = None
        try:
            ip = socket.gethostbyname(host)
        except OSError:
            ip = host
        if lib is not None:
            self._h = lib.pt_store_client_connect(
                ip.encode(), port, int(timeout * 1000))
            if self._h is None:
                raise ConnectionError(
                    f"cannot reach store at {host}:{port}")
        else:
            deadline = time.monotonic() + timeout
            while True:
                try:
                    self._sock = socket.create_connection((ip, port),
                                                          timeout=timeout)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.05)
            self._sock_lock = threading.Lock()

    # -- python-fallback framing --
    def _py_call(self, op, key, val=None):
        msg = pickle.dumps((op, key, val))
        with self._sock_lock:
            self._sock.sendall(len(msg).to_bytes(4, "little") + msg)
            head = self._sock.recv(4, socket.MSG_WAITALL)
            n = int.from_bytes(head, "little")
            buf = b""
            while len(buf) < n:
                buf += self._sock.recv(n - len(buf))
        return pickle.loads(buf)

    def set(self, key: str, value):
        if isinstance(value, str):
            value = value.encode()
        if self._h is not None:
            rc = self._lib.pt_store_set(self._h, key.encode(), value,
                                        len(value))
            if rc != 0:
                raise ConnectionError("store set failed")
        else:
            self._py_call("set", key, value)

    def get(self, key: str) -> bytes:
        if self._h is not None:
            size = 1 << 16
            while True:
                buf = ctypes.create_string_buffer(size)
                n = self._lib.pt_store_get(self._h, key.encode(), buf,
                                           len(buf))
                if n < 0:
                    raise ConnectionError("store get failed")
                if n <= len(buf):
                    return ctypes.string_at(buf, n)
                # value larger than the buffer (and may grow between
                # fetches — loop until a fetch fits)
                size = n * 2
        return self._py_call("get", key)

    def add(self, key: str, delta: int) -> int:
        if self._h is not None:
            r = self._lib.pt_store_add(self._h, key.encode(), delta)
            if r == -(2 ** 63):
                raise ConnectionError("store add failed")
            return int(r)
        return int.from_bytes(self._py_call("add", key, delta), "little",
                              signed=True)

    def wait(self, key: str):
        if self._h is not None:
            if self._lib.pt_store_wait(self._h, key.encode()) != 0:
                raise ConnectionError("store wait failed")
        else:
            self._py_call("wait", key)

    def check(self, key: str) -> bool:
        if self._h is not None:
            return self._lib.pt_store_check(self._h, key.encode()) == 1
        return self._py_call("check", key) == b"\1"

    def delete_key(self, key: str):
        if self._h is not None:
            self._lib.pt_store_delete(self._h, key.encode())
        else:
            self._py_call("delete", key)

    def barrier(self, name: str = "_barrier"):
        """All world_size clients rendezvous; generation counter makes the
        barrier reusable."""
        arrived = self.add(f"{name}/cnt", 1)
        gen = (arrived - 1) // self.world_size
        if arrived % self.world_size == 0:
            self.set(f"{name}/gen{gen}", b"1")
        self.wait(f"{name}/gen{gen}")

    def close(self):
        if self._h is not None:
            self._lib.pt_store_client_close(self._h)
            self._h = None
        if self._sock is not None:
            self._sock.close()
            self._sock = None
        if self._daemon is not None:
            self._daemon.stop()
            self._daemon = None


# ---------------------------------------------------------------------------
# Shared-memory queue
# ---------------------------------------------------------------------------

class ShmQueue:
    """Cross-process byte-message ring in POSIX shared memory. The
    DataLoader puts pickled (or raw numpy) batches through this with one
    memcpy each way, instead of re-pickling over a pipe."""

    def __init__(self, name: str, capacity: int = 64 << 20,
                 create: bool = True):
        self.name = name if name.startswith("/") else "/" + name
        self._lib = load_native()
        self._h = None
        self._py = None
        self._capacity = capacity
        self._buf = None           # reusable receive buffer
        if self._lib is not None:
            if create:
                self._h = self._lib.pt_shmq_create(self.name.encode(),
                                                   capacity)
            else:
                self._h = self._lib.pt_shmq_open(self.name.encode())
            if self._h is None:
                raise OSError(f"shm queue {self.name!r} unavailable")
        else:
            # fallback: multiprocessing queue has the same interface shape
            import multiprocessing
            self._py = multiprocessing.Queue()

    @property
    def is_native(self):
        return self._h is not None

    def put(self, data: bytes, timeout: Optional[float] = None):
        if self._h is not None:
            rc = self._lib.pt_shmq_push(
                self._h, data, len(data),
                -1 if timeout is None else int(timeout * 1000))
            if rc == -2:
                raise ValueError(
                    f"message of {len(data)} bytes exceeds queue capacity")
            if rc != 0:
                raise TimeoutError("shm queue push timed out")
        else:
            import queue as _q
            try:
                self._py.put(data, timeout=timeout)
            except _q.Full:
                raise TimeoutError("shm queue push timed out") from None

    def get(self, timeout: Optional[float] = None) -> bytes:
        if self._h is not None:
            # one message can be at most capacity bytes; reuse the buffer
            if self._buf is None:
                self._buf = ctypes.create_string_buffer(self._capacity)
            buf = self._buf
            n = self._lib.pt_shmq_pop(
                self._h, buf, len(buf),
                -1 if timeout is None else int(timeout * 1000))
            if n == -1:
                raise TimeoutError("shm queue pop timed out")
            if n == -2:
                raise ValueError(
                    "message exceeded this handle's capacity "
                    f"({self._capacity}B) and was dropped — open both ends "
                    "with the same capacity")
            # an owned copy of n bytes: .raw would copy the whole capacity
            return ctypes.string_at(buf, n)
        import queue as _q
        try:
            return self._py.get(timeout=timeout)
        except _q.Empty:
            raise TimeoutError("shm queue pop timed out") from None

    def qsize_bytes(self) -> int:
        if self._h is not None:
            return int(self._lib.pt_shmq_size(self._h))
        return -1

    def close(self):
        if self._h is not None:
            self._lib.pt_shmq_close(self._h)
            self._h = None
