"""Durable fleet control plane (serving/durability.py + fleet.py):
write-ahead journal of control-plane transitions (length-framed,
CRC32-trailed, fsync'd — the PR 15 wire frame discipline on disk),
coordinated fleet checkpoints committed by one atomic manifest rename,
a disk spill tier for watermark-evicted prefix chains, and the
headline pin: a whole fleet killed MID-DECODE — streams queued,
mid-chunked-prefill, shipped-in-transit, adopted-and-decoding —
recovers via ``Fleet.recover`` with every completed stream
BIT-IDENTICAL to an uncrashed run (greedy AND seeded-sampled; dense,
paged, paged+kv_int8), compile counts still 1 on the reused arenas,
zero block leaks, exactly one terminal per request across pre- and
post-crash state, and a torn journal tail truncated LOUDLY."""
import json
import os
import struct

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import checkpoint as _ckpt
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (ContinuousBatchingEngine, DecodeWorker,
                                Fleet, PrefillDenseEngine,
                                PrefillPagedEngine, PrefillWorker,
                                PrefixSpillStore, RequestFailure,
                                Server, WriteAheadJournal)
from paddle_tpu.serving import durability as dur
from paddle_tpu.utils import faults


@pytest.fixture(scope="module")
def setup():
    """One tiny model + paged 2-prefill/2-decode engines, plus dense
    and kv_int8 single-prefill sets for the recovery matrix. reset()
    frees slots/blocks, never the compiled programs — so a 'crashed'
    fleet's engines stand in for a fresh process that re-traces once."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    kw = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)
    pf = [PrefillPagedEngine(model, **kw) for _ in range(2)]
    dc = [ContinuousBatchingEngine(model, paged=True, **kw)
          for _ in range(2)]
    pf_d = PrefillDenseEngine(model, num_slots=2, max_len=64,
                              decode_block=4, prompt_buckets=(8, 16, 32))
    dc_d = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                    decode_block=4,
                                    prompt_buckets=(8, 16, 32))
    pf_8 = PrefillPagedEngine(model, kv_int8=True, **kw)
    dc_8 = ContinuousBatchingEngine(model, paged=True, kv_int8=True,
                                    **kw)
    return model, cfg, pf, dc, (pf_d, dc_d), (pf_8, dc_8)


@pytest.fixture(autouse=True)
def _disarmed():
    faults.clear()
    yield
    faults.clear()


def _ref(model, prompt, max_new, **kw):
    return model.generate(paddle.to_tensor(prompt[None, :]),
                          max_new_tokens=max_new, **kw).numpy()[0]


def _prompts(cfg, seed, lens):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
            for L in lens]


def _reset(*engines):
    for e in engines:
        e.reset()


def _factory(by_name):
    """engine_factory for Fleet.recover backed by the (reset) fixture
    engines — the same compiled programs a restarted process would
    re-trace, minus the tracing cost."""
    def make(role, name):
        return by_name[name]
    return make


def _check_clean(fleet):
    assert not fleet.busy()
    for w in fleet.prefill + fleet.decode:
        assert all(s is None for s in w.engine._slots)
        if hasattr(w.engine, "manager"):
            assert not w.engine.manager._ref
            w.engine.manager.assert_consistent()
    for w in fleet.prefill:
        assert not w.engine._outbox


def _terminal_owner_count(fleet, rid):
    """How many places hold the rid's terminal — the exactly-one pin
    across pre/post-crash state (worker results ledgers are restored
    snapshots; _local_results/_failures are the fleet's own)."""
    n = sum(1 for w in fleet.prefill + fleet.decode
            if rid in w.server.results)
    n += int(rid in fleet._local_results)
    n += int(rid in fleet._failures)
    return n


# ---------------------------------------------------------------------------
# the write-ahead journal: framing, replay, torn tails
# ---------------------------------------------------------------------------

class TestJournal:
    def test_append_replay_roundtrip(self, tmp_path):
        p = str(tmp_path / "j.log")
        j = WriteAheadJournal(p)
        recs = [{"k": "submit", "rid": 7, "prompt": [1, 2, 3]},
                {"k": "progress", "rid": 7, "base": 0, "ext": [4, 5]},
                {"k": "terminal", "rid": 7, "tokens": [1, 2, 3, 4, 5]}]
        for r in recs:
            j.append(r)
        j.close()
        got, torn = WriteAheadJournal.replay(p)
        assert not torn
        assert got == recs

    def test_reopen_continues_seq(self, tmp_path):
        p = str(tmp_path / "j.log")
        j = WriteAheadJournal(p)
        j.append({"k": "a"})
        j.append({"k": "b"})
        j.close()
        j2 = WriteAheadJournal(p)
        assert j2.seq == 2
        j2.append({"k": "c"})
        j2.close()
        got, torn = WriteAheadJournal.replay(p)
        assert not torn
        assert [r["k"] for r in got] == ["a", "b", "c"]

    def test_torn_tail_truncated_loudly(self, tmp_path):
        """An armed ``journal.torn_tail`` leaves a half-written frame;
        replay warns, counts it, truncates the file back to the last
        valid frame boundary — a second replay is clean."""
        p = str(tmp_path / "j.log")
        j = WriteAheadJournal(p)
        j.append({"k": "a"})
        j.append({"k": "b"})
        with faults.injected("journal.torn_tail:at=1"):
            with pytest.raises(faults.InjectedFault):
                j.append({"k": "lost"})
        j.close()
        with pytest.warns(RuntimeWarning, match="torn"):
            got, torn = WriteAheadJournal.replay(p)
        assert torn
        assert [r["k"] for r in got] == ["a", "b"]
        got2, torn2 = WriteAheadJournal.replay(p)
        assert not torn2 and [r["k"] for r in got2] == ["a", "b"]
        # the truncated segment reopens append-ready at seq 2
        j3 = WriteAheadJournal(p)
        assert j3.seq == 2
        j3.close()

    def test_crc_flip_truncates_at_corrupt_frame(self, tmp_path):
        p = str(tmp_path / "j.log")
        j = WriteAheadJournal(p)
        offsets = []
        for k in ("a", "b", "c"):
            offsets.append(os.path.getsize(p) if os.path.exists(p)
                           else 0)
            j.append({"k": k})
        j.close()
        with open(p, "r+b") as f:       # flip one payload byte of "b"
            f.seek(offsets[1] + 16 + 2)
            b = f.read(1)
            f.seek(offsets[1] + 16 + 2)
            f.write(bytes([b[0] ^ 0xFF]))
        with pytest.warns(RuntimeWarning):
            got, torn = WriteAheadJournal.replay(p)
        assert torn
        assert [r["k"] for r in got] == ["a"]

    def test_journal_write_fault_is_retried_by_the_fleet(self, setup,
                                                        tmp_path):
        """A transient ``journal.write`` fault never loses a record:
        the fleet retries the append outside the handoff breaker."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        fleet = Fleet([PrefillWorker(e) for e in pf],
                      [DecodeWorker(e) for e in dc],
                      durability=str(tmp_path / "d"))
        (p,) = _prompts(cfg, 3, (9,))
        with faults.injected("journal.write:at=1"):
            rid = fleet.submit(p, max_new_tokens=6)
        res = fleet.run_until_idle(max_ticks=200)
        np.testing.assert_array_equal(res[rid], _ref(model, p, 6))
        recs, torn = WriteAheadJournal.replay(
            dur.journal_path(str(tmp_path / "d"), 0))
        assert not torn
        assert any(r.get("k") == "submit" and r["rid"] == rid
                   for r in recs)

    def test_journal_write_fault_past_budget_is_fatal(self, setup,
                                                      tmp_path):
        """Durability is a hard contract: a journal that stays broken
        past the retry budget fails the operation loudly instead of
        silently running without a log."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        fleet = Fleet([PrefillWorker(e) for e in pf],
                      [DecodeWorker(e) for e in dc],
                      durability=str(tmp_path / "d"))
        (p,) = _prompts(cfg, 3, (9,))
        with faults.injected("journal.write:every=1"):
            with pytest.raises(RuntimeError, match="journal"):
                fleet.submit(p, max_new_tokens=6)


# ---------------------------------------------------------------------------
# satellite 1: hardened atomic helpers + checkpoint commit fault
# ---------------------------------------------------------------------------

class TestAtomicHelpers:
    def test_atomic_write_fsyncs_parent_directory(self, tmp_path,
                                                  monkeypatch):
        """The rename is only durable once the PARENT DIRECTORY is
        fsynced — the regression this PR fixes."""
        calls = []
        real = _ckpt._fsync_dir
        monkeypatch.setattr(_ckpt, "_fsync_dir",
                            lambda d: (calls.append(d), real(d)))
        path = str(tmp_path / "x.json")
        _ckpt.atomic_json_dump(path, {"a": 1})
        assert calls == [str(tmp_path)]
        assert json.load(open(path)) == {"a": 1}

    def test_commit_fault_leaves_no_manifest(self, setup, tmp_path):
        """An armed ``checkpoint.commit`` dies BEFORE the manifest
        rename: no manifest of the new epoch exists, the journal keeps
        its records, and the fleet stays recoverable from them."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        d = str(tmp_path / "d")
        fleet = Fleet([PrefillWorker(e) for e in pf],
                      [DecodeWorker(e) for e in dc], durability=d)
        (p,) = _prompts(cfg, 5, (9,))
        rid = fleet.submit(p, max_new_tokens=6)
        fleet.tick()
        with faults.injected("checkpoint.commit:at=1"):
            with pytest.raises(faults.InjectedFault):
                fleet.checkpoint()
        assert dur.list_epochs(d, "manifest") == []
        assert fleet._dur_epoch == 0    # the rotation never happened
        del fleet
        _reset(*pf, *dc)
        by_name = {f"prefill{i}": e for i, e in enumerate(pf)}
        by_name.update({f"decode{i}": e for i, e in enumerate(dc)})
        fleet2 = Fleet.recover(d, engine_factory=_factory(by_name))
        res = fleet2.run_until_idle(max_ticks=300)
        np.testing.assert_array_equal(res[rid], _ref(model, p, 6))


# ---------------------------------------------------------------------------
# un-shipped outboxes ride the snapshot (the lifted PR 5 restriction)
# ---------------------------------------------------------------------------

class TestOutboxSnapshot:
    def test_unshipped_outbox_roundtrips_bit_identical(self, setup,
                                                       tmp_path):
        """A prefill server snapshotted WITH un-shipped handoffs in
        its outbox — previously refused — restores them, and a fleet
        built over the restored server ships and completes them
        bit-identically."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        w = PrefillWorker(pf[0], name="prefill0")
        (p,) = _prompts(cfg, 11, (13,))
        rid = w.server.submit(p, max_new_tokens=8)
        for _ in range(30):
            w.tick()
            if w.engine._outbox:
                break
        assert w.engine._outbox, "prefill must park an un-shipped " \
            "handoff for this test to mean anything"
        ph0 = w.engine._outbox[0]
        tok0, key0 = ph0.tok0, np.array(ph0.key)
        prompt0 = np.array(ph0.prompt)
        path = str(tmp_path / "pf.npz")
        w.server.snapshot(path)
        _reset(pf[0])
        assert not pf[0]._outbox
        srv = Server.restore(path, pf[0])
        assert len(pf[0]._outbox) == 1
        ph1 = pf[0]._outbox[0]
        assert ph1.tok0 == tok0
        np.testing.assert_array_equal(ph1.key, key0)
        np.testing.assert_array_equal(ph1.prompt, prompt0)
        pf[0].manager.assert_consistent()
        fleet = Fleet([PrefillWorker(pf[0], name="prefill0",
                                     server=srv)],
                      [DecodeWorker(dc[0])])
        fleet._requests[rid] = {"prompt": np.asarray(p, np.int32),
                                "worker": "prefill0", "t_submit": 0.0,
                                "kw": {"max_new_tokens": 8}}
        res = fleet.run_until_idle(max_ticks=300)
        np.testing.assert_array_equal(res[rid], _ref(model, p, 8))
        _check_clean(fleet)


# ---------------------------------------------------------------------------
# the disk spill tier
# ---------------------------------------------------------------------------

class TestSpillTier:
    def _warm(self, fleet, model, cfg, p, mn=6):
        rid = fleet.submit(p, max_new_tokens=mn)
        res = fleet.run_until_idle(max_ticks=300)
        np.testing.assert_array_equal(res[rid], _ref(model, p, mn))
        return rid

    def test_extract_chain_store_roundtrip(self, setup, tmp_path):
        """extract_chain is side-effect-free (no LRU/hit perturbation)
        and the store round-trips it CRC-verified; slicing past a
        local match drops exactly the matched rows."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        fleet = Fleet([PrefillWorker(pf[0])], [DecodeWorker(dc[0])])
        (p,) = _prompts(cfg, 21, (17,))
        self._warm(fleet, model, cfg, p)
        m = pf[0].manager
        chains = m.registered_chains()
        assert chains
        depth = max(chains.values())
        hits_before = dict(m._hits)
        tok_map = m.chain_tokens_map()
        digest = next(d for d, k in chains.items() if k == depth)
        toks = tok_map[digest]
        h = dur.extract_chain(pf[0], toks, depth, source="prefill0")
        assert h is not None
        assert dict(m._hits) == hits_before, \
            "extraction must not perturb eviction order"
        store = PrefixSpillStore(str(tmp_path / "spill"))
        assert store.put(digest, h)
        # the lookup walk mirrors deepest_covered: only full blocks
        # BEFORE the last token count, so probe with a continuation
        probe = np.asarray(list(toks) + [0], np.int32)
        sdepth, sdig = store.lookup(probe, pf[0].kv_block_size,
                                    m.hash_fn)
        assert (sdepth, sdig) == (depth, digest)
        h2 = store.read(digest)
        h2.verify_crc()
        np.testing.assert_array_equal(h2.arrays["tokens"],
                                      h.arrays["tokens"])
        sliced = dur.slice_prefix_payload(h2, 1)
        assert sliced.meta["skip"] == 1
        assert "crc32" not in sliced.meta
        for k, a in sliced.arrays.items():
            if k != "tokens":
                assert a.shape[0] == depth - 1

    def test_watermark_eviction_spills_then_spill_hit(self, setup,
                                                      tmp_path):
        """Chains evicted by the fleet watermark land in the spill
        tier; after a full fleet restart (cold arenas, empty
        directory) the same prompt is served from disk — a spill hit,
        bit-identical output."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        d = str(tmp_path / "d")
        (p,) = _prompts(cfg, 23, (17,))
        fleet = Fleet([PrefillWorker(pf[0], name="prefill0")],
                      [DecodeWorker(dc[0], name="decode0")],
                      durability=d, evict_high=0.02, evict_low=0.01)
        self._warm(fleet, model, cfg, p)
        fleet.tick()                    # idle tick runs the eviction
        assert fleet._spill is not None
        assert fleet._spill.stats()["writes"] >= 1
        assert fleet.prefix_evictions >= 1
        del fleet
        _reset(pf[0], dc[0])
        fleet2 = Fleet([PrefillWorker(pf[0], name="prefill0")],
                       [DecodeWorker(dc[0], name="decode0")],
                       durability=d)
        self._warm(fleet2, model, cfg, p)
        st = fleet2.stats()["durability"]["spill"]
        assert st["hits"] >= 1, st
        assert fleet2.prefix_fetches >= 1
        _check_clean(fleet2)

    def test_spill_read_fault_falls_back_bit_identical(self, setup,
                                                       tmp_path):
        """Armed ``spill.read``: the fetch counts a miss and the
        request prefills locally — same tokens, no failure."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        d = str(tmp_path / "d")
        (p,) = _prompts(cfg, 23, (17,))
        fleet = Fleet([PrefillWorker(pf[0], name="prefill0")],
                      [DecodeWorker(dc[0], name="decode0")],
                      durability=d, evict_high=0.02, evict_low=0.01)
        self._warm(fleet, model, cfg, p)
        fleet.tick()
        assert fleet._spill.stats()["writes"] >= 1
        del fleet
        _reset(pf[0], dc[0])
        fleet2 = Fleet([PrefillWorker(pf[0], name="prefill0")],
                       [DecodeWorker(dc[0], name="decode0")],
                       durability=d)
        with faults.injected("spill.read:every=1"):
            self._warm(fleet2, model, cfg, p)
        st = fleet2.stats()["durability"]["spill"]
        assert st["hits"] == 0 and st["misses"] >= 1, st
        assert fleet2.prefix_fetch_failures.get("spill", 0) >= 1
        _check_clean(fleet2)

    def test_lru_byte_cap_evicts_oldest(self, tmp_path):
        from paddle_tpu.serving import KVHandoff, encode_handoff

        def mk():
            rs = np.random.RandomState(0)
            return KVHandoff(
                meta={"format": dur.FETCH_FORMAT,
                      "kind": "prefix", "n_blocks": 1,
                      "skip": 0, "block_size": 8, "kv_int8": False,
                      "leaf_specs": [], "src_tp_degree": 1},
                arrays={"tokens": rs.randint(
                    0, 100, (8,)).astype(np.int32)})
        one = len(encode_handoff(mk()))
        # room for one entry (+ the crc32 stamp put adds), not two
        store = PrefixSpillStore(str(tmp_path / "s"),
                                 max_bytes=one + one // 2)
        for i in range(3):
            assert store.put(bytes([i]) * 20, mk())
        assert len(store) == 1          # only the newest survives
        assert store.stats()["evictions"] == 2
        # a blob that alone exceeds the cap is refused outright
        tiny = PrefixSpillStore(str(tmp_path / "t"), max_bytes=1)
        assert not tiny.put(b"x" * 20, mk())
        assert len(tiny) == 0


# ---------------------------------------------------------------------------
# the headline: whole-fleet crash, Fleet.recover, bit-identity
# ---------------------------------------------------------------------------

class TestWholeFleetRecovery:
    def _crash_recover(self, model, cfg, pfs, dcs, d, samples=(),
                       news=(10, 12, 9, 11), pre_ticks=4,
                       post_ticks=2, checkpoint=True):
        """Submit, checkpoint mid-traffic, submit MORE, crash with
        streams in every state, recover onto reset engines, run to
        idle. Returns (fleet2, expected {rid: ref_row})."""
        prompts = _prompts(cfg, 41, (9, 13, 17, 11))
        fleet = Fleet([PrefillWorker(e) for e in pfs],
                      [DecodeWorker(e) for e in dcs], durability=d)
        expect = {}
        for p, mn in zip(prompts[:2], news[:2]):
            expect[fleet.submit(p, max_new_tokens=mn)] = \
                _ref(model, p, mn)
        for _ in range(pre_ticks):
            fleet.tick()
        if checkpoint:
            fleet.checkpoint()
        for p, mn in zip(prompts[2:], news[2:]):
            expect[fleet.submit(p, max_new_tokens=mn)] = \
                _ref(model, p, mn)
        for p, mn, kw in samples:
            expect[fleet.submit(p, max_new_tokens=mn, **kw)] = \
                _ref(model, p, mn, do_sample=True, **kw)
        for _ in range(post_ticks):
            fleet.tick()
        # -- CRASH: the fleet object and every arena die; only the
        # durability directory survives --
        del fleet
        _reset(*pfs, *dcs)
        by_name = {f"prefill{i}": e for i, e in enumerate(pfs)}
        by_name.update({f"decode{i}": e for i, e in enumerate(dcs)})
        fleet2 = Fleet.recover(d, engine_factory=_factory(by_name))
        assert fleet2.recoveries == 1
        fleet2.run_until_idle(max_ticks=500)
        return fleet2, expect

    def _assert_recovered(self, fleet2, expect):
        res = fleet2.results
        for rid, ref in expect.items():
            v = res.get(rid)
            assert v is not None and not isinstance(v, RequestFailure),\
                f"rid {rid}: {v}"
            np.testing.assert_array_equal(v, ref)
            assert _terminal_owner_count(fleet2, rid) == 1, rid
        _check_clean(fleet2)

    def test_paged_recover_bit_identical_greedy_and_sampled(
            self, setup, tmp_path):
        """THE headline pin (paged): checkpoint mid-traffic, crash two
        ticks later with queued + mid-prefill + in-transit + adopted
        streams, recover — every row bit-identical, decode compiles
        still 1, zero leaks, one terminal per request."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        prompts = _prompts(cfg, 43, (7, 12))
        samples = [(prompts[0], 10,
                    dict(temperature=0.9, top_k=40, seed=11)),
                   (prompts[1], 8,
                    dict(temperature=1.1, top_p=0.9, seed=3))]
        fleet2, expect = self._crash_recover(
            model, cfg, pf, dc, str(tmp_path / "d"), samples=samples)
        self._assert_recovered(fleet2, expect)
        for d_ in fleet2.decode:
            assert d_.engine.decode_compile_count() == 1
        assert fleet2.last_recovery["redriven"] >= 1
        assert fleet2.stats()["durability"]["recoveries"] == 1

    def test_kv_int8_recover_bit_identical(self, setup, tmp_path):
        model, cfg, _pf, dc, _dense, (pf_8, dc_8) = setup
        _reset(pf_8, dc_8)
        fleet2, expect = self._crash_recover(
            model, cfg, [pf_8], [dc_8], str(tmp_path / "d"))
        self._assert_recovered(fleet2, expect)
        assert fleet2.decode[0].engine.decode_compile_count() == 1

    def test_dense_recover_bit_identical(self, setup, tmp_path):
        model, cfg, _pf, _dc, (pf_d, dc_d), _ = setup
        _reset(pf_d, dc_d)
        fleet2, expect = self._crash_recover(
            model, cfg, [pf_d], [dc_d], str(tmp_path / "d"))
        self._assert_recovered(fleet2, expect)

    def test_journal_only_recovery_without_any_checkpoint(
            self, setup, tmp_path):
        """No checkpoint ever committed: recovery rebuilds the fleet
        from the genesis record + the journal alone."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        fleet2, expect = self._crash_recover(
            model, cfg, pf, dc, str(tmp_path / "d"), checkpoint=False,
            pre_ticks=2, post_ticks=1)
        self._assert_recovered(fleet2, expect)
        assert fleet2.last_recovery["epoch"] == 0

    def test_torn_tail_recovery_is_loud_and_bit_identical(
            self, setup, tmp_path):
        """Crash mid-append: the torn frame is truncated LOUDLY and
        the lost record's stream still completes bit-identically (its
        effect redrives from the surviving records)."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        d = str(tmp_path / "d")
        prompts = _prompts(cfg, 41, (9, 13, 17, 11))
        fleet = Fleet([PrefillWorker(e) for e in pf],
                      [DecodeWorker(e) for e in dc], durability=d)
        expect = {}
        for p in prompts:
            expect[fleet.submit(p, max_new_tokens=10)] = \
                _ref(model, p, 10)
        for _ in range(3):
            fleet.tick()
        fleet.checkpoint()
        with faults.injected("journal.torn_tail:at=1"):
            for _ in range(3):          # a progress/terminal append
                fleet.tick()            # tears mid-write; _jrec's
        del fleet                       # retried copy is lost too
        _reset(*pf, *dc)
        by_name = {f"prefill{i}": e for i, e in enumerate(pf)}
        by_name.update({f"decode{i}": e for i, e in enumerate(dc)})
        with pytest.warns(RuntimeWarning, match="torn"):
            fleet2 = Fleet.recover(d, engine_factory=_factory(by_name))
        assert fleet2.last_recovery["torn_tail"] is True
        fleet2.run_until_idle(max_ticks=500)
        res = fleet2.results
        for rid, ref in expect.items():
            np.testing.assert_array_equal(res[rid], ref)
            assert _terminal_owner_count(fleet2, rid) == 1
        _check_clean(fleet2)

    def test_scale_records_replay_onto_manifest_topology(
            self, setup, tmp_path):
        """Journal scale records overlay the manifest topology: a
        decode worker drained and removed AFTER the checkpoint stays
        gone at recovery."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        d = str(tmp_path / "d")
        fleet = Fleet([PrefillWorker(e) for e in pf],
                      [DecodeWorker(e) for e in dc], durability=d)
        (p,) = _prompts(cfg, 47, (9,))
        rid = fleet.submit(p, max_new_tokens=6)
        fleet.run_until_idle(max_ticks=300)
        fleet.checkpoint()
        fleet.drain_decode_worker(1)
        fleet.remove_decode_worker(1)
        del fleet
        _reset(*pf, *dc)
        by_name = {f"prefill{i}": e for i, e in enumerate(pf)}
        by_name["decode0"] = dc[0]
        fleet2 = Fleet.recover(d, engine_factory=_factory(by_name))
        assert [w.name for w in fleet2.decode] == ["decode0"]
        np.testing.assert_array_equal(fleet2.results[rid],
                                      _ref(model, p, 6))
        # the recovered (shrunken) fleet still serves
        (q,) = _prompts(cfg, 48, (11,))
        rid2 = fleet2.submit(q, max_new_tokens=6)
        assert rid2 > rid, "recovered allocators must never reuse rids"
        res = fleet2.run_until_idle(max_ticks=300)
        np.testing.assert_array_equal(res[rid2], _ref(model, q, 6))

    def test_flight_ring_survives_with_continuing_seqs(self, setup,
                                                       tmp_path):
        """Satellite 6: the fleet-level flight ring rides the manifest
        — restored events keep their seqs, the checkpoint/recovered
        markers are present, and post-recovery events continue the
        numbering (the Server contract from PR 6, now fleet-wide)."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        d = str(tmp_path / "d")
        fleet = Fleet([PrefillWorker(e) for e in pf],
                      [DecodeWorker(e) for e in dc], durability=d)
        (p,) = _prompts(cfg, 51, (9,))
        fleet.submit(p, max_new_tokens=6)
        for _ in range(3):
            fleet.tick()
        fleet.checkpoint()
        pre_total = fleet.flight.recorded_total()
        del fleet
        _reset(*pf, *dc)
        by_name = {f"prefill{i}": e for i, e in enumerate(pf)}
        by_name.update({f"decode{i}": e for i, e in enumerate(dc)})
        fleet2 = Fleet.recover(d, engine_factory=_factory(by_name))
        kinds = [e["kind"] for e in fleet2.flight.events()]
        assert "checkpoint" in kinds and "recovered" in kinds
        seqs = [e["seq"] for e in fleet2.flight.events()]
        assert seqs == sorted(seqs)
        assert fleet2.flight.recorded_total() >= pre_total + 1


# ---------------------------------------------------------------------------
# satellite 2: metric families are catalog-complete at zero
# ---------------------------------------------------------------------------

class TestMetricsCatalog:
    def test_families_registered_at_import(self):
        from paddle_tpu.observability import metrics as om
        fams = om.render_prometheus()
        for name in ("pt_journal_appends_total",
                     "pt_journal_bytes_total",
                     "pt_journal_replays_total",
                     "pt_journal_torn_tails_total",
                     "pt_checkpoint_commits_total",
                     "pt_checkpoint_recoveries_total",
                     "pt_prefix_spill_writes_total",
                     "pt_prefix_spill_hits_total",
                     "pt_prefix_spill_misses_total"):
            assert name in fams, name


def test_recovery_soak_entry_point_runs_and_reports(run_soak):
    """``tools/recovery_soak.sh`` at its smallest: checkpoint, crash,
    ``Fleet.recover``, and every key the script asserts on or prints."""
    out = run_soak("run_serving_recovery_bench", requests=2, max_new=4)
    assert out["serving_recovery_completed"] \
        == out["serving_recovery_requests"] == 2
    assert out["serving_recovery_bit_identical"]
    assert out["serving_recovery_decode_compiles"] == 1
    assert out["serving_recovery_leaks"] == 0
    assert out["serving_recovery_journal_replayed"] >= 1
    assert {"serving_recovery_redriven",
            "serving_recovery_recover_wall_s"} <= set(out)
