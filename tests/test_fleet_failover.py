"""Fleet failure domains (serving/transport.py + fleet.py): the real
localhost-TCP Transport (length-framed, CRC32-trailed, seq-numbered,
acked, reconnecting, at-least-once), worker health via heartbeat leases
(N missed beats = dead), idempotent adoption ((rid, payload seq) dedup
at exact refcounts; tampered-CRC payloads refused pre-allocation), and
the headline pin: a decode worker killed MID-DECODE over the socket
transport with ~1% wire faults armed has every lost stream redriven —
re-prefilled on a surviving prefill worker via a ``redrive``
ResumeState with the heartbeat-carried tokens and the host-replayed rng
key — and completes BIT-IDENTICAL to an unfailed run (greedy AND
seeded-sampled; 16- and 8-token blocks, kv_int8), compile counts still 1,
zero block leaks on every surviving arena, and exactly one terminal per
request across every worker's trace."""
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import ObservabilityConfig
from paddle_tpu.serving import (ContinuousBatchingEngine, DecodeWorker,
                                Fleet, PrefillPagedEngine, PrefillWorker,
                                Request, RequestFailure, ResumeState,
                                Server, SocketTransport, TransportError,
                                decode_handoff, encode_handoff)
from paddle_tpu.utils import faults

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ~1% per-site wire faults — the headline's ambient noise
WIRE_FAULTS = ("transport.partial_write:p=0.01;"
               "transport.corrupt:p=0.01;transport.disconnect:p=0.01")


@pytest.fixture(scope="module")
def setup():
    """One model + 2-prefill/2-decode engines at 8-token blocks, a
    1-prefill/2-decode set at the default 16-token geometry and an int8
    1-prefill/2-decode set (every kill test needs a SURVIVING decode
    worker). reset() frees slots/blocks, never the compiled programs."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    kw = dict(num_slots=2, max_len=64, decode_block=4, block_size=8,
              prefill_chunk=8)
    pf = [PrefillPagedEngine(model, **kw) for _ in range(2)]
    dc = [ContinuousBatchingEngine(model, paged=True, **kw)
          for _ in range(2)]
    pf_d = PrefillPagedEngine(model, num_slots=2, max_len=64,
                              decode_block=4)
    dc_d = [ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                     decode_block=4)
            for _ in range(2)]
    pf_8 = PrefillPagedEngine(model, kv_int8=True, **kw)
    dc_8 = [ContinuousBatchingEngine(model, paged=True, kv_int8=True,
                                     **kw) for _ in range(2)]
    return model, cfg, pf, dc, (pf_d, dc_d), (pf_8, dc_8)


@pytest.fixture(autouse=True)
def _disarmed():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def transport():
    t = SocketTransport("fleet", io_timeout_s=5.0,
                        retry_backoff_s=0.001)
    yield t
    t.close()


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


def _fleet(pf_engines, dc_engines, transport, trace=False, **kw):
    obs = ObservabilityConfig(trace_requests=True) if trace else None
    return Fleet([PrefillWorker(e, observability=obs)
                  for e in pf_engines],
                 [DecodeWorker(e, observability=obs)
                  for e in dc_engines],
                 transport=transport, **kw)


def _check_clean_survivors(fleet):
    """Zero-leak teardown on every LIVE worker (a dead worker's arena
    is unreadable junk by contract)."""
    assert not fleet.busy()
    for w in fleet.prefill:
        if not fleet._alive(w.name):
            continue
        assert not w.engine._outbox
        assert all(s is None for s in w.engine._slots)
        if hasattr(w.engine, "manager"):
            assert not w.engine.manager._ref
            w.engine.manager.assert_consistent()
    for d in fleet.decode:
        if not fleet._alive(d.name):
            continue
        assert all(s is None for s in d.engine._slots)
        if hasattr(d.engine, "manager"):
            assert not d.engine.manager._ref
            d.engine.manager.assert_consistent()


def _terminal_counts(fleet):
    """rid -> total terminal spans across EVERY worker's tracer."""
    counts = {}
    servers = [w.server for w in fleet.prefill] \
        + [d.server for d in fleet.decode]
    for srv in servers:
        for rid, terms in srv.tracer.terminal_states().items():
            counts.setdefault(rid, []).extend(terms)
    return counts


# ---------------------------------------------------------------------------
# the socket transport alone (no model, cheap)
# ---------------------------------------------------------------------------

class TestSocketTransport:
    def test_roundtrip_fifo_counters_and_pending(self, transport):
        t = transport
        t.send("w1", b"payload-one")
        t.send("w1", b"payload-two")
        t.send("w2", b"other-worker")
        assert t.pending() == 3
        assert t.recv("w1") == b"payload-one"
        assert t.recv("w1") == b"payload-two"
        assert t.recv("w2") == b"other-worker"
        assert t.recv("w1") is None
        assert t.pending() == 0
        st = t.stats()
        assert st["sends"] == 3 and st["resends"] == 0
        assert st["bytes_sent"] == len(b"payload-one")  \
            + len(b"payload-two") + len(b"other-worker")

    def test_corrupt_frame_dropped_by_crc_then_retransmitted(
            self, transport):
        t = transport
        with faults.injected("transport.corrupt:at=1"):
            t.send("w1", b"corrupt-me-please")
        assert t.recv("w1") == b"corrupt-me-please"
        assert t.recv("w1") is None         # exactly once
        assert t.crc_drops >= 1 and t.resends >= 1

    def test_partial_write_reconnects_and_retransmits(self, transport):
        t = transport
        with faults.injected("transport.partial_write:at=1"):
            t.send("w1", b"torn-write-payload")
        assert t.recv("w1") == b"torn-write-payload"
        assert t.recv("w1") is None
        assert t.reconnects >= 1

    def test_disconnect_before_ack_delivers_duplicate(self, transport):
        """The at-least-once pin: an ack-lost frame is retransmitted
        and the receiver (which cannot know across a reconnect) hands
        BOTH copies up — exactly the duplicate adopt() must dedup."""
        t = transport
        with faults.injected("transport.disconnect:at=1"):
            t.send("w1", b"dup-me")
        got = []
        while True:
            d = t.recv("w1")
            if d is None:
                break
            got.append(d)
        assert got == [b"dup-me", b"dup-me"]
        assert t.resends >= 1

    def test_exhausted_retry_budget_raises_transport_error(self):
        t = SocketTransport("fleet", retry_attempts=1,
                            retry_backoff_s=0.001)
        try:
            with faults.injected("transport.corrupt:every=1"):
                with pytest.raises(TransportError, match="failed"):
                    t.send("w1", b"never-arrives-intact")
            assert t.recv("w1") is None
        finally:
            t.close()

    def test_drop_endpoint_discards_then_recreates(self, transport):
        t = transport
        t.send("w1", b"doomed")
        t.drop_endpoint("w1")
        assert t.recv("w1") is None         # fresh endpoint, empty
        t.send("w1", b"successor")          # same name works again
        assert t.recv("w1") == b"successor"

    def test_closed_transport_refuses(self):
        t = SocketTransport("fleet")
        t.close()
        with pytest.raises(TransportError, match="closed"):
            t.send("w1", b"x")


class TestFaultSiteTable:
    def test_every_armed_site_appears_in_the_docstring_table(self):
        """The faults.py docstring table is the operator's site
        catalog; a site threaded into code but missing from the table
        is invisible to whoever arms PT_FAULTS."""
        pat = re.compile(
            r"(?:fault_point|should_fire)\(\s*[\"']([a-z_.]+)[\"']")
        sites = set()
        for dirpath, _dirs, files in os.walk(
                os.path.join(ROOT, "paddle_tpu")):
            for fn in files:
                if fn.endswith(".py"):
                    with open(os.path.join(dirpath, fn)) as f:
                        sites.update(pat.findall(f.read()))
        assert sites, "no fault sites found — grep pattern broken?"
        missing = {s for s in sites if s not in faults.__doc__}
        assert not missing, \
            f"sites threaded in code but absent from the table: " \
            f"{sorted(missing)}"
        for s in ("transport.partial_write", "transport.corrupt",
                  "transport.disconnect"):
            assert s in sites, f"{s} no longer threaded"


# ---------------------------------------------------------------------------
# adoption idempotency in isolation
# ---------------------------------------------------------------------------

class TestAdoptIdempotency:
    def _shipped_payload(self, pf_engine, prompt, seq=1, **kw):
        """Prefill one request and produce the exact wire bytes the
        fleet would ship (seq + CRC stamped)."""
        w = PrefillWorker(pf_engine)
        w.server.submit(prompt, **kw)
        for _ in range(6):
            w.tick()
        (ph,) = pf_engine.take_handoffs()
        h = pf_engine.extract_handoff(ph, source="t")
        h.meta["seq"] = seq
        h.meta["crc32"] = h.payload_crc32()
        data = encode_handoff(h)
        pf_engine.release_handoff(ph)
        return data

    def test_duplicate_adopt_is_noop_at_exact_refcounts(self, setup):
        model, cfg, pf, dc, *_ = setup
        _reset(pf[0], dc[0])
        p = _prompts(cfg, 21, (9,))[0]
        data = self._shipped_payload(pf[0], p, max_new_tokens=6)
        d = DecodeWorker(dc[0], name="d")
        assert d.adopt(decode_handoff(data)) == DecodeWorker.ADOPTED
        mgr = dc[0].manager
        usable_after_first = mgr.usable_blocks()
        ref_after_first = dict(mgr._ref)
        live_after_first = len(dc[0].live_runs())
        # the SAME payload bytes again — an ack-lost retransmit
        assert d.adopt(decode_handoff(data)) == DecodeWorker.DUPLICATE
        assert d.duplicate_adopts == 1
        assert mgr.usable_blocks() == usable_after_first
        assert dict(mgr._ref) == ref_after_first
        assert len(dc[0].live_runs()) == live_after_first
        mgr.assert_consistent()
        # and the armed stream still completes bit-identically
        res = d.server.run_until_idle()
        (rid,) = res.keys()
        np.testing.assert_array_equal(
            res[rid], _ref(model, p, 6, temperature=0.0))
        mgr.assert_consistent()

    def test_tampered_crc_refused_before_any_allocation(self, setup):
        model, cfg, pf, dc, *_ = setup
        _reset(pf[0], dc[1])
        p = _prompts(cfg, 22, (9,))[0]
        data = self._shipped_payload(pf[0], p, max_new_tokens=6)
        h = decode_handoff(data)
        kv_keys = [k for k in h.arrays if k.startswith("kv_")]
        arr = np.array(h.arrays[kv_keys[0]])   # writable copy
        arr.flat[0] = arr.flat[0] + 1          # one corrupted element
        h.arrays[kv_keys[0]] = arr
        d = DecodeWorker(dc[1], name="d")
        usable0 = dc[1].manager.usable_blocks()
        with pytest.raises(ValueError, match="CRC mismatch"):
            d.adopt(h)
        assert dc[1].manager.usable_blocks() == usable0  # nothing moved
        assert not dc[1].manager._ref                    # no refs taken
        assert not dc[1].has_live()
        dc[1].manager.assert_consistent()

    def test_adopt_on_killed_worker_raises_transport_error(
            self, setup):
        model, cfg, pf, dc, *_ = setup
        _reset(pf[0], dc[0])
        p = _prompts(cfg, 23, (5,))[0]
        data = self._shipped_payload(pf[0], p, max_new_tokens=4)
        d = DecodeWorker(dc[0], name="d")
        d.kill()
        with pytest.raises(TransportError, match="dead"):
            d.adopt(decode_handoff(data))


# ---------------------------------------------------------------------------
# satellite 1: prefill workers take REDRIVE resumes, nothing else
# ---------------------------------------------------------------------------

class TestPrefillRedriveResume:
    def test_user_preemption_resume_still_refused(self, setup):
        """Regression pin: the PR 14 refusal (message and all)
        survives for non-redrive resumes at both geometries."""
        model, cfg, pf, dc, (pf_d, dc_d), _ = setup
        _reset(pf[0], pf_d)
        req = Request(request_id=1, prompt=np.ones((5,), np.int32),
                      max_new_tokens=8,
                      resume=ResumeState(tokens=[1, 2],
                                         key=np.zeros(2, np.uint32)))
        with pytest.raises(NotImplementedError,
                           match="do not take preemption resumes"):
            pf[0].try_admit(req)
        with pytest.raises(NotImplementedError,
                           match="do not take preemption resumes"):
            pf_d.try_admit(req)

    @pytest.mark.parametrize("flavour", ["paged"])
    def test_redrive_resume_parks_carried_history_in_outbox(
            self, setup, flavour):
        model, cfg, pf, dc, _, _ = setup
        eng = pf[0]
        _reset(eng)
        prompt = _prompts(cfg, 24, (9,))[0]
        toks = [7, 11, 13]
        key = np.asarray([123, 456], np.uint32)
        req = Request(request_id=42, prompt=prompt, max_new_tokens=10,
                      resume=ResumeState(tokens=toks, key=key,
                                         t_admit=1.5, redrive=True))
        w = PrefillWorker(eng)
        w.server.inject(req)
        for _ in range(8):
            w.tick()
        (ph,) = eng.take_handoffs()
        h = eng.extract_handoff(ph, source="t")
        assert h.meta["tokens"] == toks
        assert h.meta["orig_prompt_len"] == int(prompt.size)
        assert h.meta["tok0"] == toks[-1]
        assert h.meta["rem0"] == 10 - len(toks)
        np.testing.assert_array_equal(
            np.asarray(h.arrays["key"], np.uint32), key)
        # the prefilled sequence is prompt + tokens[:-1]
        np.testing.assert_array_equal(
            h.arrays["prompt"],
            np.concatenate([prompt,
                            np.asarray(toks[:-1], np.int32)]))
        eng.release_handoff(ph)
        eng.manager.assert_consistent()


# ---------------------------------------------------------------------------
# the headline: kill a decode worker mid-decode, redrive, bit-identity
# ---------------------------------------------------------------------------

class TestRedriveBitIdentity:
    def _run_kill(self, fleet, model, prompts, news, samples=(),
                  kill_idx=1, kill_after=3, max_ticks=500):
        """Submit, tick until the victim owns streams, kill it, run to
        idle. Returns (rids, sampled_rids, results)."""
        rids = [fleet.submit(p, max_new_tokens=mn)
                for p, mn in zip(prompts, news)]
        srids = [fleet.submit(p, max_new_tokens=mn, **kw)
                 for p, mn, kw in samples]
        for _ in range(kill_after):
            fleet.tick()
        assert fleet.decode[kill_idx].engine.has_live(), \
            "the victim must own streams mid-decode at the kill"
        fleet.kill_decode_worker(kill_idx)
        res = fleet.run_until_idle(max_ticks=max_ticks)
        return rids, srids, res

    def test_paged_kill_mid_decode_bit_identical_under_wire_faults(
            self, setup, transport):
        """THE headline pin: paged fleet over the socket transport,
        ~1% wire faults armed, one decode worker killed mid-decode —
        every stream (incl. the redriven ones) completes BIT-IDENTICAL
        to generate(), greedy AND seeded-sampled, compile counts still
        1, survivors leak-free, exactly one terminal per request
        across every worker's trace."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        prompts = _prompts(cfg, 31, (5, 9, 12, 7))
        news = [24, 20, 24, 22]
        samples = [(prompts[0], 20,
                    dict(temperature=0.9, top_k=40, seed=11)),
                   (prompts[2], 18,
                    dict(temperature=1.1, top_p=0.9, seed=3))]
        fleet = _fleet(pf, dc, transport, trace=True, lease_misses=2)
        with faults.injected(WIRE_FAULTS, seed=7):
            rids, srids, res = self._run_kill(
                fleet, model, prompts, news, samples)
        st = fleet.stats()
        assert st["workers_lost"] == 1
        assert st["redrives"] >= 1, "the kill must have cost streams"
        assert st["worker_states"]["decode1"] == "dead"
        for rid, p, mn in zip(rids, prompts, news):
            assert not isinstance(res[rid], RequestFailure), \
                f"{rid}: {res[rid]}"
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, mn, temperature=0.0))
        for srid, (p, mn, kw) in zip(srids, samples):
            np.testing.assert_array_equal(
                res[srid], _ref(model, p, mn, do_sample=True, **kw))
        assert dc[0].decode_compile_count() == 1
        for w in fleet.prefill:
            assert w.engine.prefill_compile_count() == 1
        # exactly one terminal per request across the WHOLE fleet's
        # traces (the dead worker's trace stays open, terminal-free)
        terms = _terminal_counts(fleet)
        for rid in rids + srids:
            assert len(terms.get(rid, [])) == 1, \
                f"rid {rid}: terminals {terms.get(rid)}"
        assert st["redrive_latency_p50_s"] is not None
        # the lease machinery left its audit trail in the flight ring
        kinds = {e["kind"] for e in fleet.flight.events()}
        assert {"heartbeat_miss", "worker_dead", "redrive"} <= kinds
        _check_clean_survivors(fleet)

    def test_dense_kill_mid_decode_bit_identical(self, setup,
                                                 transport):
        model, cfg, _, _, (pf_d, dc_d), _ = setup
        _reset(pf_d, *dc_d)
        prompts = _prompts(cfg, 32, (5, 9, 12))
        news = [20, 24, 20]
        samples = [(prompts[1], 16,
                    dict(temperature=0.9, top_k=40, seed=7))]
        fleet = _fleet([pf_d], dc_d, transport, lease_misses=2)
        with faults.injected(WIRE_FAULTS, seed=9):
            rids, srids, res = self._run_kill(
                fleet, model, prompts, news, samples)
        assert fleet.stats()["redrives"] >= 1
        for rid, p, mn in zip(rids, prompts, news):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, mn, temperature=0.0))
        np.testing.assert_array_equal(
            res[srids[0]], _ref(model, prompts[1], 16, do_sample=True,
                                temperature=0.9, top_k=40, seed=7))
        assert dc_d[0].decode_compile_count() == 1
        _check_clean_survivors(fleet)

    def test_paged_kv_int8_kill_bit_identical(self, setup, transport):
        """The fully quantized stack survives worker loss: int8 codes
        redrive across the socket wire and the recovered stream equals
        an unfailed int8 single-replica run token for token."""
        model, cfg, _, _, _, (pf_8, dc_8) = setup
        _reset(pf_8, *dc_8)
        prompts = _prompts(cfg, 33, (5, 9, 12))
        news = [20, 24, 20]
        fleet = _fleet([pf_8], dc_8, transport, lease_misses=2)
        with faults.injected(WIRE_FAULTS, seed=11):
            rids, _, res = self._run_kill(fleet, model, prompts, news)
        assert fleet.stats()["redrives"] >= 1
        # unfailed int8 twin on the surviving engine (already
        # compiled; int8 streams are compared against themselves)
        _reset(dc_8[0])
        srv = Server(dc_8[0])
        trids = [srv.submit(p, max_new_tokens=mn)
                 for p, mn in zip(prompts, news)]
        tres = srv.run_until_idle()
        for rid, trid in zip(rids, trids):
            assert not isinstance(res[rid], RequestFailure), \
                f"{rid}: {res[rid]}"
            np.testing.assert_array_equal(res[rid], tres[trid])
        assert dc_8[0].decode_compile_count() == 1
        _check_clean_survivors(fleet)

    def test_kill_before_adoption_redrives_in_transit_payloads(
            self, setup, transport):
        """Payloads sitting in a dead worker's endpoint queue (shipped
        but never adopted) redrive exactly like adopted streams — the
        fleet's records, not the wire, are the source of truth."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        prompts = _prompts(cfg, 34, (9, 12))
        fleet = _fleet(pf, dc, transport, lease_misses=2)
        rids = [fleet.submit(p, max_new_tokens=12) for p in prompts]
        fleet.tick()                 # prefills underway, nothing
        fleet.kill_decode_worker(1)  # adopted on decode1 yet
        res = fleet.run_until_idle(max_ticks=300)
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 12, temperature=0.0))
        assert fleet.stats()["workers_lost"] == 1
        _check_clean_survivors(fleet)

    def test_no_surviving_decode_worker_fails_explicitly(
            self, setup, transport):
        model, cfg, pf, dc, *_ = setup
        _reset(pf[0], dc[0])
        prompts = _prompts(cfg, 35, (5, 9))
        fleet = _fleet([pf[0]], [dc[0]], transport, lease_misses=1)
        rids = [fleet.submit(p, max_new_tokens=20) for p in prompts]
        for _ in range(3):
            fleet.tick()
        fleet.kill_decode_worker(0)
        res = fleet.run_until_idle(max_ticks=100)
        for rid in rids:
            v = res.get(rid)
            assert isinstance(v, RequestFailure) \
                and v.reason == "worker_lost", f"{rid}: {v}"
        assert not fleet.busy()      # no hang on a dead fleet

    def test_prefill_worker_death_resubmits_unshipped_requests(
            self, setup, transport):
        """A dead PREFILL worker's queued/unshipped requests resubmit
        from the fleet's submission records under their original ids
        and complete bit-identically on the survivor."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        prompts = _prompts(cfg, 36, (5, 9, 12, 7, 6, 10))
        fleet = _fleet(pf, dc, transport, lease_misses=2,
                       spill_depth=100)
        rids = [fleet.submit(p, max_new_tokens=8) for p in prompts]
        victims = {rid for rid in rids if rid // 1_000_000 == 1}
        assert victims, "affinity sent nothing to prefill0 — reseed"
        fleet.kill_prefill_worker(0)
        res = fleet.run_until_idle(max_ticks=300)
        for rid, p in zip(rids, prompts):
            assert not isinstance(res[rid], RequestFailure), \
                f"{rid}: {res[rid]}"
            np.testing.assert_array_equal(
                res[rid], _ref(model, p, 8, temperature=0.0))
        st = fleet.stats()
        assert st["workers_lost"] == 1
        assert st["worker_states"]["prefill0"] == "dead"
        _check_clean_survivors(fleet)

    def test_in_process_transport_still_serves_the_fleet(self, setup):
        """The PR 14 default transport keeps working untouched (the
        socket transport is opt-in)."""
        model, cfg, pf, dc, *_ = setup
        _reset(*pf, *dc)
        p = _prompts(cfg, 37, (9,))[0]
        fleet = Fleet([PrefillWorker(pf[0])], [DecodeWorker(dc[0])])
        rid = fleet.submit(p, max_new_tokens=6)
        res = fleet.run_until_idle(max_ticks=100)
        np.testing.assert_array_equal(
            res[rid], _ref(model, p, 6, temperature=0.0))


def test_kill_soak_entry_point_runs_and_reports(run_soak):
    """``tools/chaos.sh``'s first phase at its smallest: the entry point
    imports, kills its worker, asserts bit-identity and zero leaks
    inside, and returns the keys the script prints."""
    out = run_soak("run_fleet_kill_soak", kills=1, requests=3, max_new=8)
    assert out["soak_completed"] + out["soak_failed"] \
        == out["soak_requests"] == 3
    assert (out["soak_kills"], out["soak_workers_lost"],
            out["soak_leaks"]) == (1, 1, 0)
    assert {"soak_seed", "soak_redrives", "soak_duplicate_adopts",
            "soak_transport", "soak_ticks"} <= set(out)
