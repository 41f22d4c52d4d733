"""Pallas block-size autotuner (ops/pallas/autotune.py):

- table round trip: record → provenance-stamped JSON → trace-time
  lookup, keyed per kernel/device-kind/params;
- staleness contract: a stamp whose jaxlib version or device kind
  disagrees with the running environment is refused (warned once,
  counted as ``stale``), and record() onto a stale table starts fresh
  instead of mixing provenances;
- consumers: xent's ``_best_chunk`` cap (tuned when present, the
  documented 4096 fallback regression-pinned otherwise), the paged
  engine's default arena block size, and flash/splash block preference
  resolution (env > tuned > default) with the effective choice
  attributable via ``last_block_choice``.
"""
import json

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import autotune as at


@pytest.fixture()
def table(tmp_path, monkeypatch):
    path = str(tmp_path / "tune_table.json")
    monkeypatch.setenv("PT_TUNE_TABLE", path)
    at._CACHE.clear()
    at._WARNED.clear()
    yield path
    at._CACHE.clear()
    at._WARNED.clear()


class TestTable:
    def test_record_lookup_round_trip(self, table):
        at.record("xent", {"vocab": 4096}, {"chunk_cap": 1024}, 1.5,
                  candidates=4)
        got = at.lookup("xent", {"vocab": 4096})
        assert got == {"chunk_cap": 1024}
        assert at.lookup("xent", {"vocab": 8192}) is None   # other key
        stamp = at.load_table()["stamp"]
        for field in ("jax_version", "jaxlib_version", "device_kind",
                      "git_rev", "tuned_utc"):
            assert field in stamp
        assert at.stamp_matches(stamp)[0]

    def test_stale_stamp_refused_and_warned(self, table):
        at.record("xent", {"vocab": 4096}, {"chunk_cap": 1024}, 1.5)
        t = at.load_table()
        t["stamp"]["jaxlib_version"] = "0.0.0"
        with open(table, "w") as f:
            json.dump(t, f)
        at._CACHE.clear()
        at._WARNED.clear()
        with pytest.warns(RuntimeWarning, match="STALE"):
            assert at.lookup("xent", {"vocab": 4096}) is None
        # warned once per path, still refused on the second lookup
        assert at.lookup("xent", {"vocab": 4096}) is None

    def test_record_replaces_stale_table(self, table):
        at.record("xent", {"vocab": 4096}, {"chunk_cap": 1024}, 1.5)
        t = at.load_table()
        t["stamp"]["device_kind"] = "TPU v99"
        with open(table, "w") as f:
            json.dump(t, f)
        at._CACHE.clear()
        at.record("xent", {"vocab": 8192}, {"chunk_cap": 512}, 2.0)
        fresh = at.load_table()
        # the stale entry is gone (never mixed), the new one stamped now
        assert list(fresh["entries"]) == [
            at._entry_key("xent", {"vocab": 8192})]
        assert at.stamp_matches(fresh["stamp"])[0]

    def test_missing_table_is_a_miss(self, table):
        assert at.load_table() is None
        assert at.lookup("xent", {"vocab": 4096}) is None


class TestConsumers:
    def test_xent_chunk_default_unchanged_without_table(self, table):
        from paddle_tpu.ops.pallas.xent import _best_chunk
        # the documented fallback: largest divisor <= 4096
        assert _best_chunk(8192) == 4096
        assert _best_chunk(2048) == 2048
        assert _best_chunk(12288) == 4096

    def test_xent_chunk_consults_tuned_cap(self, table):
        from paddle_tpu.ops.pallas.xent import _best_chunk
        at.record("xent", {"vocab": 8192}, {"chunk_cap": 512}, 1.0)
        assert _best_chunk(8192) == 512
        assert _best_chunk(4096) == 4096       # other vocab: default

    def test_xent_tuned_fallback_matches_scan_math(self, table):
        """A tuned cap changes the schedule, never the numbers."""
        from paddle_tpu.ops.pallas.xent import _rows_scan_fwd
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(8, 2048).astype(np.float32))
        lab = jnp.asarray(rs.randint(0, 2048, (8,)).astype(np.int32))
        ref = _rows_scan_fwd(x, lab, chunk_cap=2048)
        at.record("xent", {"vocab": 2048}, {"chunk_cap": 512}, 1.0)
        got = _rows_scan_fwd(x, lab)
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(ref[0]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(got[1]),
                                   np.asarray(ref[1]), atol=1e-5)

    def test_paged_block_size_default_and_tuned(self, table):
        assert at.tuned_paged_block_size() == 16
        at.record("paged_attention", {"knob": "block_size"},
                  {"block_size": 32}, 1.0)
        assert at.tuned_paged_block_size() == 32

    def test_flash_block_pref_resolution_order(self, table,
                                               monkeypatch):
        from paddle_tpu.ops.pallas.flash_attention import _block_pref
        # default
        assert _block_pref("PT_SPLASH_BLOCK", "splash", 1024, 128) == \
            (512, "default")
        # tuned beats default
        at.record("flash_attention", {"seq": 1024, "dim": 128},
                  {"block_q": 256, "block_kv": 256}, 1.0)
        assert _block_pref("PT_SPLASH_BLOCK", "splash", 1024, 128) == \
            (256, "tuned")
        # env beats tuned (routed through flags.env_int; 0 = kernel
        # defaults is a valid explicit choice)
        monkeypatch.setenv("PT_SPLASH_BLOCK", "128")
        assert _block_pref("PT_SPLASH_BLOCK", "splash", 1024, 128) == \
            (128, "env")
        monkeypatch.setenv("PT_SPLASH_BLOCK", "0")
        assert _block_pref("PT_SPLASH_BLOCK", "splash", 1024, 128) == \
            (0, "env")


class TestSweep:
    def test_xent_sweep_records_and_is_consulted(self, table):
        from paddle_tpu.ops.pallas.xent import _tuned_chunk_cap
        out = at.autotune_xent(rows=16, vocab=1024)
        assert out["winner"]["chunk_cap"] in (512, 1024)
        assert _tuned_chunk_cap(1024) == out["winner"]["chunk_cap"]
        assert at.load_table()["entries"]
