"""Observability subsystem (paddle_tpu/observability/): metrics
registry semantics + disabled-path inertness, per-request lifecycle
traces (exactly one terminal span per submitted request, pinned under a
seeded chaos schedule), the merged Perfetto/chrome trace artifact
(request rows + the span ring's server row and RecordEvent host spans on
one clock; the recorder itself is pinned in tests/test_span_recorder.py),
the crash flight recorder (bounded ring, circuit-open auto-dump,
snapshot/restore round-trip), metrics exposition coverage across
server/engine/paging/resilience/faults/collectives/passes, and the
profiler scheduler-gating + export/summary satellites."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as profiler
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import (FlightRecorder, ObservabilityConfig,
                                      RequestTracer, export_chrome_trace,
                                      metrics)
from paddle_tpu.serving import (ContinuousBatchingEngine, RequestFailure,
                                ResilienceConfig, Scheduler, Server)
from paddle_tpu.utils import faults


@pytest.fixture(scope="module")
def setup():
    """One model + two engines of different geometry for the whole file:
    16-token blocks with a whole prompt in one chunk, and 8-token blocks
    and chunks (reset() frees state, never the compiled programs; a
    second backend of the SAME geometry per process trips the documented
    compile-cache landmine)."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    eng16 = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                     decode_block=4)
    paged = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                     decode_block=4, paged=True,
                                     block_size=8, prefill_chunk=8)
    return model, cfg, eng16, paged


@pytest.fixture(autouse=True)
def _isolated():
    """Every test starts disarmed and with a zeroed registry, and ends
    the same way — metric samples and fault schedules must never bleed
    across tests."""
    faults.clear()
    prev = metrics.enabled()
    metrics.REGISTRY.reset()
    yield
    faults.clear()
    metrics.enable(prev)
    metrics.REGISTRY.reset()


def _prompts(cfg, seed, lens):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
            for L in lens]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_gauge_histogram_semantics(self):
        metrics.enable(True)
        c = metrics.counter("t_obs_c", "help text", labels=("site",))
        c.inc(site="a")
        c.inc(2, site="a")
        c.inc(site="b")
        assert c.value(site="a") == 3.0 and c.value(site="b") == 1.0
        with pytest.raises(ValueError):
            c.inc(-1, site="a")          # counters are monotone
        g = metrics.gauge("t_obs_g")
        g.set(7.5)
        g.inc(0.5)
        assert g.value() == 8.0
        h = metrics.histogram("t_obs_h", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.5, 5.0):
            h.observe(v)
        s = h.samples()[0]["value"]
        assert s["count"] == 4 and s["sum"] == pytest.approx(6.05)
        # cumulative: <=0.1 -> 1, <=1.0 -> 3, +Inf -> 4
        assert s["buckets"] == {"0.1": 1, "1.0": 3, "+Inf": 4}

    def test_get_or_create_identity_and_mismatch(self):
        a = metrics.counter("t_obs_same", "x", labels=("k",))
        b = metrics.counter("t_obs_same", "x", labels=("k",))
        assert a is b
        with pytest.raises(ValueError):
            metrics.gauge("t_obs_same")          # kind mismatch
        with pytest.raises(ValueError):
            metrics.counter("t_obs_same", labels=("other",))
        with pytest.raises(ValueError):
            metrics.enable(True) or a.inc(wrong="v")  # label schema

    def test_disabled_hot_path_is_inert(self):
        metrics.enable(False)
        c = metrics.counter("t_obs_dis", labels=("x",))
        h = metrics.histogram("t_obs_dis_h")
        g = metrics.gauge("t_obs_dis_g")
        c.inc(x="v")
        h.observe(1.0)
        g.set(3.0)
        # no samples were even CREATED — the first-line bool return
        assert c.samples() == [] and h.samples() == [] \
            and g.samples() == []

    def test_dump_and_prometheus_rendering(self):
        metrics.enable(True)
        metrics.counter("t_obs_render", "counts things",
                        labels=("kind",)).inc(kind='we"ird')
        metrics.histogram("t_obs_render_h", "hist",
                          buckets=(1.0,)).observe(0.5)
        d = metrics.dump()
        assert d["t_obs_render"]["kind"] == "counter"
        assert d["t_obs_render"]["samples"][0]["labels"] == {
            "kind": 'we"ird'}
        text = metrics.render_prometheus()
        assert "# TYPE t_obs_render counter" in text
        assert 't_obs_render{kind="we\\"ird"} 1.0' in text
        assert 't_obs_render_h_bucket{le="1.0"} 1' in text
        assert 't_obs_render_h_bucket{le="+Inf"} 1' in text
        assert "t_obs_render_h_count 1" in text


class TestDisabledPathInert:
    def test_disabled_stream_touches_nothing(self, setup):
        """Metrics off + tracing off: a full served stream leaves the
        registry without a single sample, records no traces, and the
        engine carries no tracer (the hot paths pay one is-None
        check)."""
        model, cfg, eng16, paged = setup
        metrics.enable(False)
        eng16.reset()
        srv = Server(eng16, observability=ObservabilityConfig(
            trace_requests=False, flight_size=0))
        for p in _prompts(cfg, 1, [5, 9]):
            srv.submit(p, max_new_tokens=4)
        srv.run_until_idle()
        assert eng16.tracer is None
        assert srv.tracer.traces == {}
        assert srv.flight.events() == []
        sampled = [k for k, v in metrics.dump().items() if v["samples"]]
        assert sampled == []


# ---------------------------------------------------------------------------
# request traces
# ---------------------------------------------------------------------------

class TestRequestTraces:
    def test_completed_request_span_lifecycle(self, setup):
        model, cfg, eng16, paged = setup
        eng16.reset()
        srv = Server(eng16, observability=ObservabilityConfig(
            trace_requests=True))
        rid = srv.submit(_prompts(cfg, 2, [6])[0], max_new_tokens=5)
        srv.run_until_idle()
        tr = srv.tracer.traces[rid]
        names = tr.span_names()
        # lifecycle order: queue wait -> prefill chunks -> decode
        # residency -> harvest -> exactly one terminal
        for want in ("queue_wait", "prefill_chunk", "decode", "harvest",
                     "terminal:completed"):
            assert want in names, (want, names)
        assert names.index("queue_wait") < names.index("prefill_chunk")
        assert tr.terminals == ["completed"]
        assert tr.open == {}

    def test_chaos_schedule_every_request_one_terminal(self, setup):
        """The acceptance invariant under injected chaos: every
        submitted request's trace reaches EXACTLY one terminal span,
        and the terminal agrees with what landed in results."""
        model, cfg, eng16, paged = setup
        paged.reset()
        res = ResilienceConfig(retry_attempts=2, retry_backoff_s=0.001,
                               breaker_threshold=64, max_queue_depth=4)
        srv = Server(paged, Scheduler(prefill_token_budget=8),
                     resilience=res,
                     observability=ObservabilityConfig(
                         trace_requests=True))
        prompts = _prompts(cfg, 3, [5, 9, 17, 4, 12, 7, 20, 6])
        with faults.injected(
                "serving.step_block:p=0.15;serving.prefill_tick:p=0.1;"
                "serving.allocate:at=2;server.tick:at=4", seed=7):
            rids = []
            for i, p in enumerate(prompts):
                rids.append(srv.submit(
                    p, max_new_tokens=4 + (i % 3),
                    arrival_step=i // 2,
                    deadline_ticks=2 if i == 5 else None))
            results = srv.run_until_idle(max_ticks=300)
        assert set(rids) == set(results)
        terms = srv.tracer.terminal_states()
        for rid in rids:
            assert len(terms[rid]) == 1, (rid, terms[rid])
            out = results[rid]
            if isinstance(out, RequestFailure):
                assert terms[rid] == [out.reason]
            else:
                assert terms[rid] == ["completed"]
            assert srv.tracer.traces[rid].open == {}
        paged.manager.assert_consistent()

    def test_shed_request_still_terminates(self, setup):
        model, cfg, eng16, paged = setup
        eng16.reset()
        srv = Server(eng16,
                     resilience=ResilienceConfig(max_queue_depth=1),
                     observability=ObservabilityConfig(
                         trace_requests=True))
        ps = _prompts(cfg, 4, [5, 5, 5])
        # arrival far in the future keeps them queued -> 3rd submit sheds
        r = [srv.submit(p, max_new_tokens=3, arrival_step=50)
             for p in ps]
        assert isinstance(srv.results[r[-1]], RequestFailure)
        assert srv.tracer.terminal_states()[r[-1]] == ["shed"]
        srv.run_until_idle()
        for rid in r:
            assert len(srv.tracer.terminal_states()[rid]) == 1


class TestMergedChromeTrace:
    def test_single_served_batch_trace_has_all_streams(self, setup,
                                                       tmp_path):
        """The acceptance artifact: ONE Perfetto-loadable chrome-trace
        JSON from one served batch containing request spans, RecordEvent
        host spans, and tick spans — all on the perf_counter clock, the
        host spans read from the one span ring (no Profiler needed; one
        armed over the same stream exports the same RecordEvent spans)."""
        model, cfg, eng16, paged = setup
        eng16.reset()
        srv = Server(eng16, observability=ObservabilityConfig(
            trace_requests=True))
        prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                                 timer_only=True)
        with prof:
            for p in _prompts(cfg, 5, [6, 11, 4]):
                srv.submit(p, max_new_tokens=6)
            srv.run_until_idle()
        path = str(tmp_path / "nested" / "serve_trace.json")
        srv.export_trace(path)
        events = json.load(open(path))["traceEvents"]
        drained = {e["name"] for e in prof._drain_events()}
        assert {"serving.decode_block", "serving.prefill_chunk",
                "serving.tick"} <= drained

        req_rows = {e["tid"] for e in events
                    if e.get("ph") == "M" and
                    str(e["args"].get("name", "")).startswith("request ")}
        assert len(req_rows) == 3        # one named row per request
        for tid in req_rows:             # each row carries real spans
            assert any(e.get("ph") == "X" and e.get("tid") == tid
                       for e in events)
        names = [e.get("name") for e in events]
        assert "queue_wait" in names and "decode" in names
        # RecordEvent host spans from the SAME engine dispatches
        assert any(n == "serving.decode_block" for n in names)
        assert any(n == "serving.prefill_chunk" for n in names)
        # tick spans (and the engine's dispatches) on the server row,
        # each carrying its tick id; nothing from before this server
        ticks = [e for e in events if e.get("name") == "serving.tick"]
        assert ticks and all(e["tid"] == 0 and e["ph"] == "X"
                             for e in ticks)
        assert [e["args"]["tick"] for e in ticks] == list(range(len(ticks)))
        assert all(e["tid"] == 0 for e in events
                   if str(e.get("name", "")).startswith("serving."))
        # aligned clocks: every span timestamp sits in one monotonic
        # window (a wall-clock mixup would land µs-epoch outliers)
        ts = [e["ts"] for e in events if e.get("ph") == "X"]
        assert max(ts) - min(ts) < 600e6   # within 10 minutes
        # thread metadata names the rows for Perfetto
        assert any(e.get("ph") == "M" and
                   e["args"].get("name") == "server" for e in events)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.record("e", i=i)
        ev = fr.events()
        assert len(ev) == 4
        assert [e["seq"] for e in ev] == [7, 8, 9, 10]
        assert fr.recorded_total() == 10

    def test_capacity_zero_disables(self):
        fr = FlightRecorder(capacity=0)
        fr.record("e")
        assert fr.events() == [] and fr.recorded_total() == 0

    def test_env_capacity_knob(self, monkeypatch):
        monkeypatch.setenv("PT_FLIGHT_RECORDER_SIZE", "3")
        fr = FlightRecorder()
        assert fr.capacity == 3

    def test_dumps_on_circuit_open(self, setup, tmp_path):
        """Breaker opens -> the black box lands on disk before the
        drain, with the failure history inside."""
        model, cfg, eng16, paged = setup
        eng16.reset()
        srv = Server(eng16,
                     resilience=ResilienceConfig(
                         retry_attempts=0, breaker_threshold=2),
                     observability=ObservabilityConfig(
                         flight_dump_dir=str(tmp_path)))
        for p in _prompts(cfg, 6, [5, 7]):
            srv.submit(p, max_new_tokens=6)
        with faults.injected("serving.step_block:every=1"):
            results = srv.run_until_idle(max_ticks=50)
        assert all(isinstance(v, RequestFailure)
                   for v in results.values())
        path = srv.flight.last_dump_path
        assert path and os.path.dirname(path) == str(tmp_path)
        dump = json.load(open(path))
        assert dump["format"] == "pt-flight-recorder"
        assert dump["reason"] == "circuit_open"
        kinds = [e["kind"] for e in dump["events"]]
        assert "step_failure" in kinds and "breaker_open" in kinds
        assert "circuit_open_drain" in kinds

    def test_snapshot_restore_roundtrip(self, setup, tmp_path):
        """The ring rides the snapshot: a restored server still holds
        the pre-kill events (and the snapshot dumped a sidecar file)."""
        model, cfg, eng16, paged = setup
        eng16.reset()
        srv = Server(eng16)
        for p in _prompts(cfg, 7, [5, 9]):
            srv.submit(p, max_new_tokens=12)
        srv.run_until_idle(max_ticks=2)        # killed mid-stream
        pre = srv.flight.events()
        assert pre, "ticks should have recorded"
        snap = str(tmp_path / "srv.npz")
        srv.snapshot(snap)
        assert os.path.exists(snap + ".flight.json")

        eng16b = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                          decode_block=4)
        srv2 = Server.restore(snap, eng16b)
        ev = srv2.flight.events()
        kinds = [e["kind"] for e in ev]
        assert "restored" in kinds
        # pre-kill history survived with original seq numbers
        pre_seqs = [e["seq"] for e in pre]
        assert [e["seq"] for e in ev if e["kind"] == "tick"][:len(pre_seqs)]
        assert ev[0]["seq"] == pre[0]["seq"]
        # and the restored stream still finishes
        res = srv2.run_until_idle()
        assert all(not isinstance(v, RequestFailure)
                   for v in res.values())


# ---------------------------------------------------------------------------
# exposition coverage (acceptance: every instrumented subsystem)
# ---------------------------------------------------------------------------

class TestMetricsCoverage:
    def test_exposition_covers_all_subsystems(self, setup):
        import jax
        from jax.sharding import Mesh
        from paddle_tpu.distributed import collectives as cc
        from paddle_tpu.passes import PassManager, default_pipeline

        model, cfg, eng16, paged = setup
        metrics.enable(True)

        # server + engine + paging + resilience (retry) + faults
        paged.reset()
        srv = Server(paged,
                     resilience=ResilienceConfig(retry_attempts=2,
                                                 retry_backoff_s=0.001))
        with faults.injected("serving.step_block:at=2"):
            for i, p in enumerate(_prompts(cfg, 8, [5, 17, 17])):
                srv.submit(p, max_new_tokens=4, arrival_step=i)
            srv.run_until_idle(max_ticks=100)

        # collectives: flat 1-device plan still counts bytes + bound
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
        cc.all_reduce(np.ones((1, 64), np.float32), ("dp",), mesh,
                      compress=None)
        cc.all_reduce(np.ones((1, 512), np.float32), ("dp",), mesh,
                      compress="int8")

        # passes: run the pipeline over a softmax so a rewrite fires
        def f(x):
            return jax.nn.softmax(x, axis=-1)

        PassManager(default_pipeline()).run(
            jax.make_jaxpr(f)(np.zeros((4, 8), np.float32)))

        d = metrics.dump()

        def sampled(name):
            return bool(d[name]["samples"])

        # one family per subsystem named in the acceptance criteria
        assert sampled("pt_server_ticks_total")              # server
        assert sampled("pt_engine_decode_steps_total")       # engine
        assert sampled("pt_paging_prefix_lookups_total")     # paging
        assert sampled("pt_server_retries_total")            # resilience
        assert sampled("pt_server_step_failures_total")
        assert sampled("pt_fault_fires_total")               # faults
        assert sampled("pt_collectives_bytes_total")         # collectives
        assert sampled("pt_collectives_int8_error_bound")
        assert sampled("pt_passes_runs_total")               # passes
        assert sampled("pt_passes_rewrites_total")
        # the prometheus text renders every family it dumped
        text = metrics.render_prometheus()
        for fam in d:
            assert f"# TYPE {fam} " in text


# ---------------------------------------------------------------------------
# profiler satellites
# ---------------------------------------------------------------------------

class TestProfilerSchedulerGating:
    def _mk(self, **kw):
        return profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                                 timer_only=True, **kw)

    def test_closed_scheduler_keeps_host_ring_silent(self):
        """Regression: start() armed the host ring unconditionally, so
        spans recorded through CLOSED warmup steps; and CLOSED->RECORD
        in step() never re-armed it."""
        import time
        p = self._mk(scheduler=profiler.make_scheduler(
            closed=1, record=1, repeat=2))
        p._drain_events()
        p.start()
        with profiler.RecordEvent("warmup"):
            time.sleep(0.001)
        p.step()                         # CLOSED -> RECORD: re-arm
        with profiler.RecordEvent("hot"):
            time.sleep(0.001)
        p.step()                         # RECORD -> CLOSED: disarm
        with profiler.RecordEvent("cold"):
            time.sleep(0.001)
        p.stop()
        assert [e["name"] for e in p._drain_events()] == ["hot"]

    def test_schedulerless_profiler_records_immediately(self):
        p = self._mk()
        p._drain_events()
        with p:
            with profiler.RecordEvent("x"):
                pass
        assert [e["name"] for e in p._drain_events()] == ["x"]


class TestProfilerExportSummary:
    def test_export_creates_parent_dirs(self, tmp_path):
        p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                              timer_only=True)
        with p:
            with profiler.RecordEvent("span"):
                pass
        path = str(tmp_path / "a" / "b" / "trace.json")
        p.export(path)
        assert json.load(open(path))["traceEvents"] is not None
        assert p._last_export == path

    def test_summary_print_table_off_returns_aggregate(self, capsys):
        import time
        p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                              timer_only=True)
        with p:
            with profiler.RecordEvent("agg_span"):
                time.sleep(0.002)
            with profiler.RecordEvent("agg_span"):
                pass
        table, agg = p.summary(print_table=False)
        assert capsys.readouterr().out == ""
        assert agg["agg_span"]["calls"] == 2
        assert agg["agg_span"]["total_us"] >= 1000
        assert "agg_span" in table

    def test_summary_prints_by_default(self, capsys):
        p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                              timer_only=True)
        with p:
            with profiler.RecordEvent("printed"):
                pass
        table, agg = p.summary()
        assert "printed" in capsys.readouterr().out


class TestEnvKnobs:
    def test_knobs_ride_flags_helpers(self, monkeypatch):
        """PT_METRICS / PT_TRACE_REQUESTS / PT_FLIGHT_RECORDER_SIZE all
        parse through utils.flags env_bool/env_int — uniform falsy
        spellings, lenient-empty ints."""
        from paddle_tpu.utils.flags import env_bool, env_int
        monkeypatch.setenv("PT_METRICS", "off")
        assert env_bool("PT_METRICS") is False
        monkeypatch.setenv("PT_TRACE_REQUESTS", "1")
        assert RequestTracer().enabled is True
        monkeypatch.setenv("PT_TRACE_REQUESTS", "no")
        assert RequestTracer().enabled is False
        monkeypatch.setenv("PT_FLIGHT_RECORDER_SIZE", " ")
        assert env_int("PT_FLIGHT_RECORDER_SIZE", 256) == 256
        assert FlightRecorder().capacity == 256
