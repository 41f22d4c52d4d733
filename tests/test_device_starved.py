"""Where the device's idle time hides from the host spans (PR 35): the
engine's ``device_starved_ns`` and the ``starved_ns`` its enqueue spans
carry, the marks on ``serving.admit`` / ``serving.decode_sync``, ``arm_ns``
on the block after a prefill's end, and the stall record on the two syncs,
on the plain paged toy and on the hybrid toy (every serve cell shares this
code; ``serving/hybrid.py`` overrides ``_reserve`` only). The names pinned
here are the ones ``benchmark/window_spans.py`` reads and PERF.md section 3
lists."""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ContinuousBatchingEngine, Scheduler, Server

STALL_FIELDS = {"stall", "over_ns", "nivcsw", "nvcsw", "majflt", "cpu_ms"}
# every span name a tick may hold: a mark is no span, so the list is PR 25's
TICK_SPANS = {"serving.tick", "serving.expire", "serving.schedule",
              "serving.admit", "serving.prefill_chunk",
              "serving.prefill_sync", "serving.decode_block",
              "serving.decode_sync", "serving.harvest", "serving.deliver"}


def _plain():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    cfg = llama_tiny_config(tensor_parallel=False)
    return cfg, LlamaForCausalLM(cfg)


def _hybrid():
    from paddle_tpu.models.mimo_v2 import (MiMoV2ForCausalLM,
                                           mimo_v2_tiny_config)
    cfg = mimo_v2_tiny_config()
    return cfg, MiMoV2ForCausalLM(cfg)


@pytest.fixture(scope="module", params=["paged", "hybrid"])
def toy(request):
    paddle.seed(0)
    cfg, model = {"paged": _plain, "hybrid": _hybrid}[request.param]()
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                   decode_block=4, paged=True,
                                   block_size=8, prefill_chunk=8)
    return cfg, eng


def serve(toy, sizes=((5, 6), (19, 6), (9, 6)), seed=0):
    """A fresh stream over the toy engine: the server, and the ring's spans
    of the run."""
    cfg, eng = toy
    eng.reset()
    srv = Server(eng, Scheduler())
    srv.stream_sink = lambda rid, tokens, done, failure: None
    rs = np.random.RandomState(seed)
    for n, new in sizes:
        srv.submit(rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32),
                   max_new_tokens=new)
    t0 = time.perf_counter()
    srv.run_until_idle()
    return srv, tracing.since(t0)


def named(spans, *names):
    return [sp for sp in spans if sp.name in names]


def test_the_counter_is_the_sum_of_the_enqueue_spans_starved_ns(toy):
    srv, spans = serve(toy)
    eng = srv.engine
    carried = [sp for sp in spans if "starved_ns" in sp.ids]
    assert carried and eng.device_starved_ns > 0
    # only the two enqueue spans carry an interval
    assert {sp.name for sp in carried} \
        <= {"serving.prefill_chunk", "serving.decode_block"}
    assert sum(sp.ids["starved_ns"] for sp in carried) \
        == eng.device_starved_ns
    stats = srv.stats()
    assert stats["device_starved_s"] \
        == round(eng.device_starved_ns / 1e9, 6)
    assert 0 < stats["device_starved_share"] <= 1
    # an interval ends where its enqueue returns: inside the span
    ticks = {t.id: t for t in named(spans, "serving.tick")}
    for sp in carried:
        assert 0 < sp.ids["starved_ns"]
        tick = ticks[sp.parent]
        assert tick.start <= sp.start and \
            sp.start + sp.dur <= tick.start + tick.dur


def test_an_engine_idle_for_want_of_requests_counts_nothing(toy):
    srv, _ = serve(toy)
    eng = srv.engine
    assert eng._drained_ns is None          # every slot retired
    before = eng.device_starved_ns
    time.sleep(0.3)                         # no request: no starvation
    cfg, _ = toy
    srv.submit(np.arange(9, dtype=np.int32) % cfg.vocab_size,
               max_new_tokens=2)
    t0 = time.perf_counter()
    srv.run_until_idle()
    first = named(tracing.since(t0), "serving.prefill_chunk")[0]
    assert "starved_ns" not in first.ids
    assert eng.device_starved_ns - before < 0.3e9


def test_an_admission_marks_where_its_time_went(toy):
    _, spans = serve(toy)
    admits = named(spans, "serving.admit")
    assert len(admits) == 3
    for sp in admits:
        assert 0 <= sp.ids["reserved_ns"] <= sp.ids["keyed_ns"] <= sp.dur


def test_a_refused_admission_has_no_marks(toy):
    """The pool cannot hold the request: the span ends after ``_reserve``
    and says ``fresh_blocks`` 0 as an admission served whole from the
    prefix index does; ``keyed_ns`` tells them apart."""
    from paddle_tpu.serving import Request
    cfg, eng = toy
    eng.reset()
    t0 = time.perf_counter()
    held = eng.manager.allocate(eng.manager.available())
    assert not eng.try_admit(Request(
        request_id=0, prompt=np.arange(19, dtype=np.int32),
        max_new_tokens=6))
    eng.manager.release(held)
    (sp,) = named(tracing.since(t0), "serving.admit")
    assert sp.ids["fresh_blocks"] == 0
    assert not {"reserved_ns", "keyed_ns"} & set(sp.ids)
    eng.reset()


def test_a_decode_sync_says_its_first_fetch_and_counts_them(toy):
    srv, spans = serve(toy)
    syncs = named(spans, "serving.decode_sync")
    assert len(syncs) == len(named(spans, "serving.decode_block")) > 0
    # tokens, lives, oks, remaining; the hybrid toy's programs count
    # into the cache's last leaf, fetched with them
    want = 5 if srv.engine._counter_names else 4
    for sp in syncs:
        assert sp.ids["fetches"] == want
        assert 0 < sp.ids["first_ns"] <= sp.dur


def test_arm_ns_rides_the_block_after_a_prefill_ends(toy):
    _, spans = serve(toy)
    ticks = named(spans, "serving.tick")
    for tick in ticks:
        mine = [sp for sp in spans if sp.parent == tick.id]
        blocks = named(mine, "serving.decode_block")
        ended = named(mine, "serving.prefill_sync")
        for b in blocks:
            assert (b.ids["arm_ns"] > 0) == bool(ended), (tick, b)
            # what it sums lies between the syncs and the block
            if ended:
                assert b.ids["arm_ns"] <= b.start - ended[0].start
    assert any(named(spans, "serving.prefill_sync"))
    assert any(b.ids["arm_ns"] == 0
               for b in named(spans, "serving.decode_block"))


def _slow_fetch_once(monkeypatch, eng, at_sync: int, seconds: float):
    """Make the ``at_sync``-th decode sync from now last ``seconds`` more:
    its last fetch sleeps."""
    calls = [0]
    fetch = eng._read_program_counters

    def slow():
        calls[0] += 1
        if calls[0] == at_sync:
            time.sleep(seconds)
        return fetch()
    monkeypatch.setattr(eng, "_read_program_counters", slow)


def test_a_slowed_sync_is_counted_once_and_says_what_the_host_did(
        toy, monkeypatch):
    cfg, eng = toy
    eng.reset()
    # sixteen decode blocks; the twelfth's sync sleeps 0.35 s
    _slow_fetch_once(monkeypatch, eng, 12, 0.35)
    srv = Server(eng, Scheduler())
    srv.submit(np.arange(9, dtype=np.int32), max_new_tokens=64 - 9)
    t0 = time.perf_counter()
    srv.run_until_idle()
    syncs = named(tracing.since(t0), "serving.decode_sync")
    assert len(syncs) >= 12
    stalled = [sp for sp in syncs if sp.ids.get("stall")]
    assert [syncs.index(sp) for sp in stalled] == [11]
    (sp,) = stalled
    assert STALL_FIELDS <= set(sp.ids) and sp.ids["stall"] == 1
    assert 0.25e9 < sp.ids["over_ns"] < sp.dur
    assert sp.ids["cpu_ms"] < 250           # the thread slept
    if os.path.exists("/proc/thread-self/schedstat"):
        assert 0 <= sp.ids["run_delay_ms"] < 350
    assert all(not STALL_FIELDS & set(s.ids) for s in syncs if s is not sp)
    assert (eng.sync_stalls, eng.sync_stall_ns) == (1, sp.ids["over_ns"])
    stats = srv.stats()
    assert stats["sync_stalls"] == 1
    assert stats["sync_stall_s"] == round(sp.ids["over_ns"] / 1e9, 6)
    events = [e for e in srv.flight.events() if e["kind"] == "sync_stall"]
    assert len(events) == 1
    tick = next(t for t in named(tracing.since(t0), "serving.tick")
                if t.id == sp.parent)
    assert events[0]["tick"] == tick.ids["tick"]
    assert events[0]["sync"] == "serving.decode_sync"
    assert {k: events[0][k] for k in STALL_FIELDS} \
        == {k: sp.ids[k] for k in STALL_FIELDS}
    assert eng.take_sync_stalls() == []     # told once


def test_a_slow_first_sync_has_no_history_to_stall_against(
        toy, monkeypatch):
    cfg, eng = toy
    eng.reset()
    _slow_fetch_once(monkeypatch, eng, 1, 0.35)
    srv = Server(eng, Scheduler())
    srv.submit(np.arange(9, dtype=np.int32), max_new_tokens=12)
    t0 = time.perf_counter()
    srv.run_until_idle()
    syncs = named(tracing.since(t0), "serving.decode_sync")
    assert syncs[0].dur > 0.35e9
    assert not any("stall" in sp.ids for sp in syncs)
    assert srv.stats()["sync_stalls"] == eng.sync_stalls == 0
    assert not [e for e in srv.flight.events() if e["kind"] == "sync_stall"]


def _shape(spans):
    """What ``self_times`` sees of a run: each tick's spans by name with
    their parents' names, in the order they ended."""
    by_id = {sp.id: sp.name for sp in spans}
    return [(sp.name, by_id.get(sp.parent)) for sp in spans]


def test_marks_add_no_span_under_a_tick(toy, monkeypatch):
    """The seven metrics that sum self times by name read the same tree
    with and without the marks: no span a tick holds has a new child, so
    ``serving.admit``'s self time is its duration and the four groups are
    the tick."""
    _, marked = serve(toy)
    assert {sp.name for sp in marked} == TICK_SPANS
    monkeypatch.setattr(tracing.Span, "mark",
                        lambda self, name: time.perf_counter_ns())
    _, bare = serve(toy)
    assert not any("keyed_ns" in sp.ids for sp in bare)
    assert _shape(marked) == _shape(bare)
    own = tracing.self_times(marked)
    assert own["serving.admit"][1] \
        == sum(sp.dur for sp in named(marked, "serving.admit"))
    ticks = named(marked, "serving.tick")
    tick_ids = {t.id for t in ticks}
    assert own["serving.tick"][1] == sum(t.dur for t in ticks) - sum(
        sp.dur for sp in marked if sp.parent in tick_ids)
