"""Native runtime core tests: TCPStore, shm queue, and their
integrations (profiler spans, multiprocess DataLoader).

Reference pattern: test/cpp_extension + test/collective store tests +
DataLoader tests — verify. Multi-process logic is exercised as N local
processes on one host, the reference's own strategy (SURVEY §4)."""
import multiprocessing
import os
import time

import numpy as np
import pytest

from paddle_tpu.core import native_available
from paddle_tpu.core.native_api import MasterDaemon, ShmQueue, TCPStore

needs_native = pytest.mark.skipif(not native_available(),
                                  reason="g++ unavailable")


class TestNativeBuild:
    """libptcore.so is a build product (never committed): built from
    ptcore.cc on first use, rebuilt when the sha256 of the source differs
    from the stamp beside the binary — not by mtime, which means nothing
    on a copied checkout."""

    @pytest.mark.parametrize("lib,stamp,lib_older,rebuilt", [
        (False, None, False, True),        # first use: no binary
        (True, "match", True, False),      # same source, older mtime
        (True, "other", False, True),      # source changed
        (True, None, False, True),         # binary without a stamp
    ], ids=["first-use", "old-mtime-same-hash", "hash-differs",
            "no-stamp"])
    def test_staleness_is_decided_by_source_hash(
            self, tmp_path, monkeypatch, lib, stamp, lib_older, rebuilt):
        import paddle_tpu.core as core
        src = tmp_path / "ptcore.cc"
        src.write_text("// source\n")
        libp = tmp_path / "libptcore.so"
        monkeypatch.setattr(core, "_SRC", str(src))
        monkeypatch.setattr(core, "_LIB", str(libp))
        monkeypatch.setattr(core, "_STAMP", str(libp) + ".src_sha256")
        monkeypatch.setattr(core, "_lib", None)
        monkeypatch.setattr(core, "_build_error", None)
        if lib:
            libp.write_bytes(b"not a library")
            if lib_older:
                os.utime(libp, (1, 1))
        if stamp is not None:
            (tmp_path / "libptcore.so.src_sha256").write_text(
                core._src_hash() if stamp == "match" else "0" * 64)
        built = []
        monkeypatch.setattr(core, "_build", built.append)
        assert core.load_native() is None     # the fake binary won't load
        assert built == ([core._src_hash()] if rebuilt else [])


class TestProfilerSpans:
    """The C++ host tracer went with PR 25 (one span store: the ring in
    observability/tracing.py); what stays pinned here is that a
    ``RecordEvent`` under a ``Profiler`` comes out of its export."""

    def test_profiler_integration(self, tmp_path):
        import paddle_tpu.profiler as profiler
        with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU]) as p:
            with profiler.RecordEvent("my_step"):
                time.sleep(0.005)
        ev = p._drain_events()
        spans = [e for e in ev if e.get("name") == "my_step"]
        assert spans and spans[0]["dur"] >= 4000


def _store_worker(rank, port, results):
    store = TCPStore("127.0.0.1", port, world_size=2)
    store.set(f"key{rank}", f"val{rank}")
    other = store.get(f"key{1 - rank}")
    n = store.add("counter", 1)
    store.barrier("b0")
    results[rank] = (other.decode(), n)
    store.close()


class TestTCPStore:
    def test_basic_kv(self):
        daemon = MasterDaemon(0)
        store = TCPStore("127.0.0.1", daemon.port)
        store.set("alpha", b"hello")
        assert store.get("alpha") == b"hello"
        assert store.check("alpha") and not store.check("nope")
        assert store.add("cnt", 5) == 5
        assert store.add("cnt", -2) == 3
        store.delete_key("alpha")
        assert not store.check("alpha")
        store.close()
        daemon.stop()

    def test_get_blocks_until_set(self):
        daemon = MasterDaemon(0)
        s1 = TCPStore("127.0.0.1", daemon.port)
        s2 = TCPStore("127.0.0.1", daemon.port)
        import threading
        got = []
        th = threading.Thread(target=lambda: got.append(s1.get("late")))
        th.start()
        time.sleep(0.1)
        assert not got  # still blocked
        s2.set("late", b"now")
        th.join(timeout=5)
        assert got == [b"now"]
        s1.close()
        s2.close()
        daemon.stop()

    def test_multiprocess_rendezvous(self):
        daemon = MasterDaemon(0)
        ctx = multiprocessing.get_context("fork")
        results = ctx.Manager().dict()
        procs = [ctx.Process(target=_store_worker,
                             args=(r, daemon.port, results))
                 for r in range(2)]
        for p in procs:
            p.start()
        for p in procs:
            p.join(timeout=30)
            assert p.exitcode == 0
        assert results[0][0] == "val1" and results[1][0] == "val0"
        assert sorted((results[0][1], results[1][1])) == [1, 2]
        daemon.stop()


def _shm_producer(name, capacity, n):
    q = ShmQueue(name, capacity=capacity, create=False)
    for i in range(n):
        payload = np.full((64,), i, np.int32).tobytes()
        q.put(payload)
    q.close()


class TestShmQueue:
    @needs_native
    def test_same_process_roundtrip(self):
        q = ShmQueue(f"pt_test_{os.getpid()}", capacity=1 << 20)
        q.put(b"abc")
        q.put(b"defgh")
        assert q.get(timeout=5) == b"abc"
        assert q.get(timeout=5) == b"defgh"
        q.close()

    @needs_native
    def test_timeout(self):
        q = ShmQueue(f"pt_to_{os.getpid()}", capacity=1 << 16)
        with pytest.raises(TimeoutError):
            q.get(timeout=0.1)
        q.close()

    @needs_native
    def test_cross_process(self):
        name = f"pt_xp_{os.getpid()}"
        cap = 1 << 20
        q = ShmQueue(name, capacity=cap, create=True)
        ctx = multiprocessing.get_context("fork")
        p = ctx.Process(target=_shm_producer, args=(name, cap, 50))
        p.start()
        seen = []
        for _ in range(50):
            buf = q.get(timeout=10)
            seen.append(int(np.frombuffer(buf, np.int32)[0]))
        p.join(timeout=10)
        assert seen == list(range(50))
        q.close()

    @needs_native
    def test_wraparound(self):
        # queue smaller than total payload: forces ring wrap + blocking
        name = f"pt_wrap_{os.getpid()}"
        cap = 4096
        q = ShmQueue(name, capacity=cap, create=True)
        ctx = multiprocessing.get_context("fork")
        p = ctx.Process(target=_shm_producer, args=(name, cap, 100))
        p.start()
        for i in range(100):
            buf = q.get(timeout=10)
            assert int(np.frombuffer(buf, np.int32)[0]) == i
        p.join(timeout=10)
        q.close()


class _SquareDataset:
    def __len__(self):
        return 64

    def __getitem__(self, i):
        return np.full((4,), i, np.float32), np.asarray([i * i], np.float32)


class TestDataLoaderMultiprocess:
    @needs_native
    def test_shared_memory_loader(self):
        import paddle_tpu.io as io
        dl = io.DataLoader(_SquareDataset(), batch_size=8, num_workers=2,
                           use_shared_memory=True)
        xs, ys = [], []
        for x, y in dl:
            assert x.shape == [8, 4]
            xs.append(x.numpy())
            ys.append(y.numpy())
        allx = np.concatenate(xs)
        assert allx.shape == (64, 4)
        np.testing.assert_array_equal(allx[:, 0], np.arange(64))
        np.testing.assert_array_equal(np.concatenate(ys)[:, 0],
                                      np.arange(64) ** 2)

    @needs_native
    def test_worker_exception_propagates(self):
        import paddle_tpu.io as io

        class Bad:
            def __len__(self):
                return 8

            def __getitem__(self, i):
                if i == 5:
                    raise RuntimeError("boom at 5")
                return np.zeros(2, np.float32)

        dl = io.DataLoader(Bad(), batch_size=2, num_workers=2,
                           use_shared_memory=True)
        with pytest.raises(RuntimeError, match="boom"):
            list(dl)

    @needs_native
    def test_worker_init_fn_and_info(self):
        import paddle_tpu.io as io

        class Probe:
            def __len__(self):
                return 4

            def __getitem__(self, i):
                info = io.get_worker_info()
                assert info is not None and info.num_workers == 2
                return np.asarray([info.id], np.int64)

        dl = io.DataLoader(Probe(), batch_size=1, num_workers=2,
                           use_shared_memory=True)
        ids = sorted(int(b.numpy()[0]) for b in dl)
        assert set(ids) <= {0, 1}


class TestStreamEventSurface:
    """L0 stream/event API parity (reference: paddle.device.cuda Stream/
    Event — on TPU, XLA owns real streams; these preserve the API)."""

    def test_event_timing(self):
        import time
        import paddle_tpu.device as device
        e1, e2 = device.Event(), device.Event()
        e1.record()
        time.sleep(0.01)
        e2.record()
        assert e1.query() and e2.query()
        assert e2.elapsed_time(e1) < 0 < e1.elapsed_time(e2)
        e1.synchronize()

    def test_stream_guard_and_events(self):
        import paddle_tpu.device as device
        s = device.Stream()
        assert device.current_stream() is not s
        with device.stream_guard(s):
            assert device.current_stream() is s
            ev = s.record_event()
            assert ev.query()
        assert device.current_stream() is not s
        s.wait_event(ev)
        s.wait_stream(device.current_stream())
        assert s.query()
        # cuda namespace aliases the same types
        assert device.cuda.Stream is device.Stream
        assert device.cuda.current_stream() is device.current_stream()

    def test_unrecorded_elapsed_raises(self):
        import pytest as _pytest
        import paddle_tpu.device as device
        with _pytest.raises(RuntimeError, match="recorded"):
            device.Event().elapsed_time(device.Event())


class TestCustomDevicePlugin:
    def test_registration_contract(self, tmp_path):
        import os
        import pytest as _pytest
        import paddle_tpu.device as device
        from paddle_tpu.utils.enforce import (NotFoundError,
                                              PreconditionNotMetError)
        with _pytest.raises(NotFoundError):
            device.register_custom_device("npu", "/nope/libfoo.so")
        lib = tmp_path / "libplugin.so"
        lib.write_bytes(b"\x7fELF")
        # backend already initialized in the test process -> must refuse
        with _pytest.raises(PreconditionNotMetError, match="initialized"):
            device.register_custom_device("npu", str(lib))
        assert device.get_all_custom_device_type() == []
