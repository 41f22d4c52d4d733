"""A request's block digests are computed once, in bulk (serving/paging.py).

``walk_chain`` is the one walk over a digest chain; ``BlockManager`` hands
every digest it computes to the caller (``find_prefix``'s ``chain``,
``extend_chain``) and a run carries them from admission to retirement, so
a block is hashed once in a request's life (``hashed_blocks`` counts).
The contract is that NOTHING else changed: the digests are the per-token
fold of ``_sha1_chain`` byte for byte, and ``PerTokenManager`` below is
the removed walk (a tuple of ``int(t)`` a block, the whole sequence again
at every call), kept here only, for the property test to drive both
managers through the same seeded lives."""
import hashlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import tracing
from paddle_tpu.serving import (ContinuousBatchingEngine, Frontend,
                                Scheduler, Server)
from paddle_tpu.serving.durability import _chain_block_ids
from paddle_tpu.serving.paging import BlockManager, _sha1_chain, walk_chain


def per_token_chain(hash_fn, tokens, bs, n_blocks):
    """The walk as it stood: one ``int()`` a token, one hash call a block."""
    out, parent = [], b""
    for j in range(n_blocks):
        chunk = tuple(int(t) for t in tokens[j * bs:(j + 1) * bs])
        parent = hash_fn(parent, chunk)
        out.append((parent, chunk))
    return out


class PerTokenManager(BlockManager):
    """The parent's ``find_prefix`` and ``chain``, as they stood; it hands
    no chain out, so every call hashes its whole sequence again."""

    def find_prefix(self, prompt, chain=None):
        bs = self.block_size
        self.lookups += 1
        found, parent = [], b""
        for j in range(self._shareable_blocks(prompt)):
            chunk = tuple(int(t) for t in prompt[j * bs:(j + 1) * bs])
            digest = self.hash_fn(parent, chunk)
            entry = self._index.get(digest)
            if entry is None or entry[1] != chunk:
                break
            found.append((digest, chunk, entry[0]))
            parent = digest
        return found

    def chain(self, tokens, n_blocks):
        return per_token_chain(self.hash_fn, tokens, self.block_size,
                               n_blocks)


# -- (a) the walker's digests ---------------------------------------------------

def _as_list(a):
    return [int(t) for t in a]


def _as_int64(a):
    return np.asarray(a, np.int64)


def _as_strided(a):
    wide = np.zeros((len(a), 3), np.int32)
    wide[:, 1] = a
    view = wide[:, 1]
    assert not view.flags["C_CONTIGUOUS"]
    return view


@pytest.mark.parametrize("given", [_as_list, _as_int64, _as_strided],
                         ids=["list", "int64", "strided"])
@pytest.mark.parametrize("bs", [8, 16, 32])
def test_walker_digests_are_the_per_token_fold(bs, given):
    rs = np.random.RandomState(bs)
    tokens = rs.randint(0, 152_576, (7 * bs + 5,)).astype(np.int32)
    want = per_token_chain(_sha1_chain, tokens, bs, 7)
    m = BlockManager(4, bs)
    seq = given(tokens)
    assert m.chain(seq, 7) == want
    assert list(walk_chain(seq, bs, 7)) == want
    # taken up from any block on, the walk gives the same digests
    assert list(walk_chain(seq, bs, 7, start=3, parent=want[2][0])) \
        == want[3:]
    carried = m.chain(seq, 2)
    assert m.extend_chain(carried, seq, 7) is carried and carried == want
    assert m.hashed_blocks == 7 + 7          # 7, then 2 + 5: none twice
    # a trailing partial block is never hashed
    assert list(walk_chain(seq, bs, 9)) == want
    assert all(type(t) is int for _, chunk in carried for t in chunk)


def test_a_callers_hash_fn_is_called_a_block_with_parent_and_tuple():
    calls = []

    def fn(parent, chunk):
        calls.append((parent, chunk))
        return hashlib.md5(parent + repr(chunk).encode()).digest()

    tokens = np.arange(100, 100 + 3 * 4 + 1, dtype=np.int64)
    m = BlockManager(8, 4, fn)
    got = m.chain(tokens, 3)
    assert got == per_token_chain(fn, tokens, 4, 3)
    assert calls[:3] == [(b"", (100, 101, 102, 103)),
                         (got[0][0], (104, 105, 106, 107)),
                         (got[1][0], (108, 109, 110, 111))]


# -- (d) a collision stops the match --------------------------------------------

def _first_token_hash(parent, chunk):
    """Collides for any two blocks that start alike."""
    return hashlib.sha1(parent + bytes([chunk[0] % 256])).digest()


@pytest.mark.parametrize("at", [0, 1, 2])
def test_a_colliding_hash_fn_stops_the_match_at_the_collision(at):
    bs = 4
    m = BlockManager(16, bs, _first_token_hash)
    a = np.arange(1, 4 * bs + 2, dtype=np.int32)
    ids = m.allocate(4)
    m.register_prefix(a, ids)
    m.release(ids)
    b = a.copy()
    b[at * bs + 2] += 1000        # same first token, another block
    before = m.hashed_blocks
    chain = []
    found = m.find_prefix(b, chain)
    assert [blk for _, _, blk in found] == ids[:at]
    # the blocks hashed: the matches and the one the match stopped at
    assert len(chain) == at + 1 == m.hashed_blocks - before
    assert chain == per_token_chain(_first_token_hash, b, bs, at + 1)
    assert m._index[chain[at][0]][1] != chain[at][1]      # a true collision
    # the carried chain registers the run's own blocks under its own tokens
    mine = m.allocate(4)
    m.register_chain(m.extend_chain(chain, b, 4), ids[:at] + mine[at:])
    assert m.hashed_blocks - before == 4
    m.release(mine)
    m.assert_consistent()


# -- (b) a random life, held to the per-token walk ------------------------------

BS = 4


def _drive(seed, num_blocks, steps):
    rs = np.random.RandomState(seed % 2 ** 32)
    new, old = BlockManager(num_blocks, BS), PerTokenManager(num_blocks, BS)
    families = [rs.randint(0, 50, (6 * BS,)).astype(np.int32)
                for _ in range(3)]
    live = []          # [prompt, block ids, carried chain, prefilled?]
    for _ in range(steps):
        op = rs.choice(["admit", "admit", "prefill_end", "prefill_end",
                        "retire", "retire", "watermark", "spill_probe"])
        if op == "admit":
            head = families[rs.randint(len(families))]
            n_blocks = rs.randint(1, 7)
            k = rs.randint(0, min(len(head), n_blocks * BS) // BS + 1) * BS
            prompt = np.concatenate(
                [head[:k], rs.randint(0, 50, (n_blocks * BS + 1 - k
                                               + rs.randint(0, BS),))])
            # half the prompts arrive as the server's int64 lists
            given = prompt.tolist() if rs.rand() < 0.5 else \
                prompt.astype(np.int32)
            chain = []
            shared = new.match_prefix(given, chain)
            assert old.match_prefix(given) == shared
            assert len(chain) == min(len(shared) + 1,
                                     (len(prompt) - 1) // BS)
            out = rs.randint(1, 3 * BS)
            need = (len(prompt) + out - 1 + BS - 1) // BS - len(shared)
            fresh, fresh_old = new.allocate(need), old.allocate(need)
            assert fresh == fresh_old
            if fresh is None:
                new.release(shared), old.release(shared)
            else:
                live.append([prompt, shared + fresh, chain, False, out])
        elif op == "prefill_end" and live:
            run = live[rs.randint(len(live))]
            prompt, ids, chain = run[:3]
            new.register_chain(new.extend_chain(
                chain, prompt, new._shareable_blocks(prompt)), ids)
            old.register_prefix(prompt, ids)
            run[3] = True
        elif op == "retire" and live:
            prompt, ids, chain, prefilled, out = live.pop(
                rs.randint(len(live)))
            if prefilled and rs.rand() < 0.8:      # a failed run: nothing
                seq = np.concatenate([prompt, rs.randint(0, 50, (out - 1,))])
                n = len(seq) // BS
                new.register_chain(new.extend_chain(chain, seq, n), ids)
                old.register_chain(old.chain(seq, n), ids)
            new.release(ids), old.release(ids)
        elif op == "watermark":
            n = rs.randint(0, 6)
            assert new.evict_cached(n) == old.evict_cached(n)
        elif op == "spill_probe" and live:
            # the spill tier's side-effect-free walk (durability.py)
            prompt, ids = live[rs.randint(len(live))][:2]
            n = rs.randint(0, len(prompt) // BS + 1)
            assert _chain_block_ids(new, prompt, n) \
                == _chain_block_ids(old, prompt, n)
        new.assert_consistent(), old.assert_consistent()
        assert new._index == old._index
        assert new._depth == old._depth
        assert new._hits == old._hits
        assert new.eviction_victims(num_blocks) \
            == old.eviction_victims(num_blocks)
        assert new.registered_chains() == old.registered_chains()
        assert (new.lookups, new.hit_blocks, new.evictions) \
            == (old.lookups, old.hit_blocks, old.evictions)
        assert new._free == old._free
    return new


@pytest.mark.parametrize("seed,num_blocks,steps", [
    (0, 12, 400), (1, 24, 600), (2, 40, 800), (2 ** 31 + 11, 64, 800),
    (5, 9, 300)])
def test_a_random_life_leaves_what_the_per_token_walk_leaves(
        seed, num_blocks, steps):
    m = _drive(seed, num_blocks, steps)
    # the arena turned over and the index was hit, many times
    assert m.evictions > steps // 5 and m.hit_blocks > steps // 40


def test_the_spill_walk_misses_on_a_partial_block():
    m = BlockManager(8, BS)
    tokens = np.arange(2 * BS + 1, dtype=np.int32)
    ids = m.allocate(2)
    m.register_prefix(tokens, ids)
    assert _chain_block_ids(m, tokens, 2) == ids
    assert _chain_block_ids(m, tuple(tokens[:2 * BS - 1]), 2) is None
    assert _chain_block_ids(m, tokens, 3) is None
    m.release(ids)


# -- (c), (e) through the engine ------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    """One model and ONE paged engine for the file (``reset()`` frees
    slots and blocks, never the compiled programs); the pool is large
    enough that nothing registered here is ever evicted."""
    paddle.seed(0)
    cfg = llama_tiny_config(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    engine = ContinuousBatchingEngine(
        model, num_slots=2, max_len=64, decode_block=4, paged=True,
        block_size=8, prefill_chunk=8, num_blocks=65)
    assert type(engine) is ContinuousBatchingEngine
    return model, cfg, engine


def _prompts(cfg, seed, lens):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _written_chains(results, bs):
    """``{digest: depth}`` of every block the finished streams wrote: a
    result row is prompt + output, and all of it but the last token is
    resident."""
    want = {}
    for row in results.values():
        seq = np.asarray(row)[:-1]
        for depth, (digest, _) in enumerate(
                per_token_chain(_sha1_chain, seq, bs, len(seq) // bs), 1):
            want[digest] = depth
    return want


# prompt length, new tokens; and of a follow-up that quotes the first
# stream's first ``quoted`` tokens
LIVES = {"within_a_block": (5, 2, None), "one_block": (9, 6, None),
         "ends_on_a_boundary": (16, 9, None), "long": (21, 20, None),
         "matched_prefix": (12, 12, 20), "matched_to_the_last": (17, 8, 24)}


@pytest.mark.parametrize("case", sorted(LIVES))
def test_hashed_blocks_is_the_blocks_a_request_wrote_or_matched(setup, case):
    model, cfg, engine = setup
    engine.reset()
    n, new, quoted = LIVES[case]
    srv = Server(engine)
    m = engine.manager
    prompt = _prompts(cfg, n, (n,))[0]
    rid = srv.submit(prompt, max_new_tokens=new)
    row = np.asarray(srv.run_until_idle()[rid])
    assert len(row) == n + new
    assert m.hashed_blocks == (n + new - 1) // 8
    assert srv.stats()["hashed_blocks"] == m.hashed_blocks
    admit = [s for s in tracing.since(0) if s.name == "serving.admit"][-1]
    assert admit.ids["hashed_blocks"] == min(1, (n - 1) // 8)   # a cold miss
    if quoted is not None:
        follow = np.concatenate([row[:quoted], _prompts(cfg, 99, (5,))[0]])
        before, shared = m.hashed_blocks, engine.shared_tokens
        rid = srv.submit(follow, max_new_tokens=6)
        srv.run_until_idle()
        matched = (engine.shared_tokens - shared) // 8
        assert matched == min(quoted, n + new - 1) // 8 > 0
        # matched or written, once each: the blocks of all it wrote
        assert m.hashed_blocks - before == (len(follow) + 6 - 1) // 8
        admit = [s for s in tracing.since(0)
                 if s.name == "serving.admit"][-1]
        assert admit.ids["hashed_blocks"] \
            == min(matched + 1, (len(follow) - 1) // 8)
    assert m.registered_chains() == _written_chains(srv.results, 8)
    assert not m._ref
    m.assert_consistent()


@pytest.fixture
def _no_compile_cache():
    """tests/test_resilience.py's workaround: a snapshot restore under the
    persistent compile cache and xdist can corrupt this jaxlib's heap."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("kill_tick", [1, 2, 4])
def test_a_restored_run_registers_what_an_uninterrupted_run_registers(
        setup, tmp_path, kill_tick, _no_compile_cache):
    """Killed mid-prefill (tick 1), at the first blocks (2), mid-decode
    (4): the restored runs carry no chain, get theirs from the same
    walker, and the index ends as the uninterrupted engine's."""
    model, cfg, engine = setup
    prompts = _prompts(cfg, 11, (5, 21, 9))

    def serve(max_ticks=None):
        engine.reset()
        srv = Server(engine, Scheduler(prefill_token_budget=8))
        for i, (p, mn) in enumerate(zip(prompts, (8, 14, 11))):
            srv.submit(p, max_new_tokens=mn, arrival_step=i)
        srv.run_until_idle(max_ticks=max_ticks)
        return srv

    ref = serve()
    want = engine.manager.registered_chains()
    want_index = dict(engine.manager._index)
    assert want == _written_chains(ref.results, 8) and len(want) == 1 + 4 + 2
    whole = engine.manager.hashed_blocks

    killed = serve(max_ticks=kill_tick)
    assert engine.has_live() or engine._jobs
    path = str(tmp_path / "paged.npz")
    killed.snapshot(path)
    hashed_before = engine.manager.hashed_blocks
    engine.reset()
    srv = Server.restore(path, engine, Scheduler(prefill_token_budget=8))
    assert all(run.chain == [] for _, run in engine.live_runs())
    res = srv.run_until_idle()
    for rid, row in ref.results.items():
        np.testing.assert_array_equal(res[rid], row)
    assert engine.manager.registered_chains() == want
    assert engine.manager._index == want_index
    # a run that lost its chain hashes its blocks again, never more
    assert whole <= hashed_before + engine.manager.hashed_blocks <= 2 * whole
    engine.manager.assert_consistent()
    assert engine.decode_compile_count() == 1


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_a_preemption_resume_registers_what_an_uninterrupted_run_registers(
        setup, sampled):
    model, cfg, engine = setup
    prompts = _prompts(cfg, 6, (5, 9, 12))
    kw = dict(temperature=0.8, top_k=20, seed=3) if sampled else {}

    def serve(preempt):
        engine.reset()
        fe = Frontend(engine, preemption=preempt)
        low = [fe.submit(p, max_new_tokens=20, priority=0, **kw)
               for p in prompts[:2]]
        fe.pump()
        fe.pump()
        hi = fe.submit(prompts[2], max_new_tokens=4,
                       priority=5 if preempt else 0, **kw)
        res = fe.run_until_idle()
        return fe, {rid: res[rid] for rid in low + [hi]}

    _, ref = serve(False)
    want = engine.manager.registered_chains()
    assert want == _written_chains(ref, 8)
    fe, res = serve(True)
    assert fe.stats()["preemptions"] >= 1 and fe.stats()["resumes"] >= 1
    for rid, row in ref.items():
        np.testing.assert_array_equal(res[rid], row)
    m = engine.manager
    assert m.registered_chains() == want
    assert {d: chunk for d, (_, chunk) in m._index.items()} == {
        d: chunk for row in ref.values()
        for d, chunk in per_token_chain(_sha1_chain, np.asarray(row)[:-1],
                                        8, (len(row) - 1) // 8)}
    assert not m._ref
    m.assert_consistent()
