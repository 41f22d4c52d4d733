"""The block manager's eviction order, held to the scan it replaced.

``BlockManager`` used to pick each victim by walking every retained
block for the minimum of ``(hit tally, LRU position)``, once per block an
admission took by eviction. It now keeps that order as blocks park and
leave (one LRU bucket per tally). The contract is that NOTHING about the
order changed: ``OldScanManager`` below is the removed scan, kept here
only, and the property test drives both through the same seeded
sequences. The work-count test makes a walk over the retained blocks an
error, so the scan cannot come back unnoticed; the snapshot tests hold
the on-disk format (``"cached"`` in LRU order, ``"hits"`` as pairs) to
what the parent wrote."""
from collections import OrderedDict

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import ContinuousBatchingEngine, Scheduler, Server
from paddle_tpu.serving.paging import BlockManager

BS = 4


class OldScanManager(BlockManager):
    """The parent's victim choice, as it stood: a walk over ``_cached``
    for each victim, a full sort for the preview. It never reads the
    order the class under test keeps (``_by_hits`` is only kept true
    beneath it, so ``assert_consistent`` holds on the reference too)."""

    def _next_victim(self) -> int:
        best, best_score = None, None
        for pos, b in enumerate(self._cached):
            score = (self._hits.get(b, 0), pos)
            if best_score is None or score < best_score:
                best, best_score = b, score
        return best

    def eviction_victims(self, n: int):
        scored = sorted(((self._hits.get(b, 0), pos, b)
                         for pos, b in enumerate(self._cached)))
        return [b for _, _, b in scored[:n]]


def _prompt(rs, families, n_blocks):
    """A prompt of ``n_blocks`` full blocks plus one token: a family's
    shared head (so prefix hits pile up unevenly) and a random tail."""
    head = families[rs.randint(len(families))]
    k = rs.randint(0, min(len(head), n_blocks * BS) // BS + 1) * BS
    tail = rs.randint(0, 50, (n_blocks * BS + 1 - k,))
    return np.concatenate([head[:k], tail]).astype(np.int32)


def _drive(seed, num_blocks, steps):
    """One seeded random life of an arena, the same calls to the new
    manager and to the old scan. Returns how many evictions it saw."""
    rs = np.random.RandomState(seed)
    new, old = BlockManager(num_blocks, BS), OldScanManager(num_blocks, BS)
    families = [rs.randint(0, 50, (6 * BS,)).astype(np.int32)
                for _ in range(3)]
    held = []                       # (prompt, block ids) of live streams
    for _ in range(steps):
        op = rs.choice(["admit", "admit", "retire", "retire", "preview",
                        "watermark", "probe"])
        if op == "admit":
            prompt = _prompt(rs, families, rs.randint(1, 7))
            shared = new.match_prefix(prompt)
            assert old.match_prefix(prompt) == shared
            need = len(prompt) // BS + rs.randint(1, 3) - len(shared)
            before = new.evictions
            fresh, fresh_old = new.allocate(need), old.allocate(need)
            assert fresh == fresh_old          # the same victims, in order
            assert old.evictions == new.evictions
            if fresh is None:
                assert new.evictions == before
                new.release(shared), old.release(shared)
            else:
                held.append((prompt, shared + fresh))
        elif op == "retire" and held:
            prompt, ids = held.pop(rs.randint(len(held)))
            if rs.rand() < 0.8:                # a failed run registers none
                n = rs.randint(0, len(prompt) // BS + 1)
                new.register_prefix(prompt, ids, n_blocks=n)
                old.register_prefix(prompt, ids, n_blocks=n)
            new.release(ids), old.release(ids)
        elif op == "preview":
            n = rs.randint(0, 12)
            want = old.eviction_victims(n)
            assert new.eviction_victims(n) == want
            # the preview IS the next n evictions, and perturbs nothing
            for m in (new, old):
                n_free = len(m._free)
                assert m.evict_cached(n) == len(want)
                assert m._free[n_free:] == want
        elif op == "watermark":
            n = rs.randint(0, 6)
            assert new.evict_cached(n) == old.evict_cached(n)
            assert new._free == old._free
        elif op == "probe" and held:
            # the fleet's fetch probe: match, then give the hold back —
            # the tallies move while the blocks are held
            prompt = held[rs.randint(len(held))][0]
            got = new.match_prefix(prompt)
            assert old.match_prefix(prompt) == got
            new.release(got), old.release(got)
        new.assert_consistent(), old.assert_consistent()
        assert list(new._cached) == list(old._cached)
        assert new._hits == old._hits
    return new.evictions


@pytest.mark.parametrize("seed,num_blocks,steps", [
    (0, 12, 400), (1, 24, 600), (2, 40, 800), (3, 40, 800),
    (2 ** 31 + 11, 64, 1000), (5, 9, 300)])
def test_same_victims_as_the_old_scan(seed, num_blocks, steps):
    evictions = _drive(seed, num_blocks, steps)
    assert evictions > steps // 20     # the arena turned over, many times


def test_no_hits_is_plain_lru():
    m = BlockManager(8, BS)
    rs = np.random.RandomState(0)
    order = []
    for _ in range(3):
        prompt = rs.randint(0, 50, (2 * BS + 1,)).astype(np.int32)
        ids = m.allocate(2)
        m.register_prefix(prompt, ids)
        m.release(ids)
        order += ids
    assert m.eviction_victims(6) == order
    assert list(m._by_hits) == [0]


class _NoWalk(OrderedDict):
    """``_cached`` with the walk made an error."""

    def __iter__(self):
        raise AssertionError("walked every retained block")

    items = keys = values = __reversed__ = __iter__


class _Counting(OrderedDict):
    touched = 0

    def __iter__(self):
        for k in super().__iter__():
            type(self).touched += 1
            yield k


def test_an_eviction_never_walks_the_retained_blocks():
    """>= 20,000 retained blocks over a few tallies, the free list empty:
    an admission's 64 blocks and the spill tier's preview touch a few
    entries each, and never iterate ``_cached``."""
    n = 20_480
    m = BlockManager(n + 1, BS)
    ids = m.allocate(n)
    assert not m._free
    rs = np.random.RandomState(0)
    m._digest_of = {b: b.to_bytes(4, "little") for b in ids}
    m._index = {d: (b, ()) for b, d in m._digest_of.items()}
    m._hits = {b: int(h) for b, h in zip(ids, rs.randint(0, 4, n)) if h}
    m.release(ids)
    m.assert_consistent()
    assert len(m._cached) == n and sorted(m._by_hits) == [0, 1, 2, 3]
    want = OldScanManager.eviction_victims(m, 64 + 8)

    m._cached = _NoWalk(m._cached)
    _Counting.touched = 0
    m._by_hits = {t: _Counting(bk) for t, bk in m._by_hits.items()}
    assert m.eviction_victims(8) == want[:8]
    assert m.allocate(64) == want[:64]
    assert m.eviction_victims(8) == want[64:]
    assert m.evictions == 64 and len(m._cached) == n - 64
    # 8 + 8 previewed, one head looked at per victim
    assert _Counting.touched <= 8 + 8 + 64


# -- the snapshot: same format, same victims after it -----------------------

@pytest.fixture(scope="module")
def tiny():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny_config(tensor_parallel=False))


def _engine(model):
    return ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                    decode_block=4, paged=True,
                                    block_size=8, prefill_chunk=8,
                                    num_blocks=13)


def _serve(engine, vocab, seed, n):
    """``n`` requests, half of them continuing a shared head, through a
    12-block arena: it turns over, with hits on the shared head."""
    rs = np.random.RandomState(seed)
    head = rs.randint(0, vocab, (17,)).astype(np.int32)
    srv = Server(engine, Scheduler())
    for i in range(n):
        tail = rs.randint(0, vocab, (rs.randint(3, 12),)).astype(np.int32)
        srv.submit(np.concatenate([head, tail]) if i % 2 else
                   rs.randint(0, vocab, (19,)).astype(np.int32),
                   max_new_tokens=6)
    srv.run_until_idle()
    return srv


def test_snapshot_round_trip_keeps_the_victims(tiny):
    vocab = tiny.config.vocab_size
    a, b = _engine(tiny), _engine(tiny)
    _serve(a, vocab, 0, 8)
    m = a.manager
    before = m.evictions
    assert before > 0 and len(m._by_hits) > 1      # turned over, with hits
    meta, arrays = a.snapshot_state()
    mm = meta["manager"]
    # the parent's format: LRU order and tally pairs, nothing new
    assert mm["cached"] == list(m._cached)
    assert sorted(map(tuple, mm["hits"])) == sorted(m._hits.items())
    assert set(mm) == {"num_blocks", "block_size", "free", "ref",
                       "digest_of", "index", "cached", "lookups",
                       "hit_blocks", "depth", "evictions", "hits"}
    b.restore_state(meta, arrays)
    b.manager.assert_consistent()
    assert {t: list(bk) for t, bk in b.manager._by_hits.items()} \
        == {t: list(bk) for t, bk in m._by_hits.items()}
    assert b.manager.eviction_victims(12) == m.eviction_victims(12)
    # and the two go on alike: same traffic, same evictions, same streams
    sa, sb = _serve(a, vocab, 1, 6), _serve(b, vocab, 1, 6)
    assert a.manager.evictions == b.manager.evictions > before
    assert list(a.manager._cached) == list(b.manager._cached)
    for rid, toks in sa.results.items():
        np.testing.assert_array_equal(toks, sb.results[rid])
    a.manager.assert_consistent(), b.manager.assert_consistent()


def test_a_parent_format_snapshot_restores(tiny):
    """A manager record with no field the parent did not write — and one
    from before the tallies existed (no ``hits``, ``depth`` or
    ``evictions``) — load, and the eviction order is rebuilt from
    ``cached`` + ``hits``."""
    vocab = tiny.config.vocab_size
    a = _engine(tiny)
    _serve(a, vocab, 2, 8)
    meta, arrays = a.snapshot_state()
    want = a.manager.eviction_victims(12)
    b = _engine(tiny)
    b.restore_state(meta, arrays)
    assert b.manager.eviction_victims(12) == want
    mm = meta["manager"]
    for key in ("hits", "depth", "evictions"):
        del mm[key]
    b.restore_state(meta, arrays)
    assert b.manager._hits == {} and b.manager.evictions == 0
    # no tallies: plain LRU, the order "cached" is written in
    assert b.manager.eviction_victims(12) == mm["cached"][:12]
    assert list(b.manager._by_hits) == ([0] if mm["cached"] else [])
