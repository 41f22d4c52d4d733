"""Block-paged KV cache for the continuous-batching engine: the shared
arena's block manager and the step backends that hold the arena.

The engine (engine.py) keeps ONE ``(num_blocks, block_size, kvh, d)``
arena per layer plus an in-graph ``(S, max_blocks)`` block table riding
the slot state (vLLM's PagedAttention restated under the repo's
static-shape rules — the table is state, the arena never reshapes):

- ``BlockManager`` (host): free list + refcounts + a rolling-hash
  prefix index. Full prompt blocks are keyed by the chain digest of
  their token contents; a later request with the same prefix maps the
  SAME arena blocks into its table and skips recomputing them. A
  retired request's registered blocks stay cached (refcount 0, LRU)
  until the pool needs them, so a hot system prompt survives across
  requests. Hash collisions are detected by comparing the stored token
  tuple and fall back to recompute. Block 0 is the reserved trash
  block: dead slots' in-graph writes are redirected there, so a block
  the host has re-allocated mid-stream can never be corrupted.
- Chunked prefill: prompts are processed through ONE compiled
  ``(1, prefill_chunk)`` program (engine.build_paged_chunk_fn) in
  chunks interleaved with decode blocks, paced by the scheduler's
  per-tick prefill token budget — a long prompt no longer stalls every
  in-flight decode for its whole length, it steals at most
  ``budget`` tokens of prefill per tick.
- Attention runs the Pallas paged-attention kernel on TPU and the
  gathered-dense reference off-TPU (ops/pallas/paged_attention.py);
  greedy streams are bit-identical to per-request ``generate()``.
- ``kv_int8=True`` stores the arena as int8 codes + per-vector fp32
  absmax scales (the EQuARX recipe from
  ``distributed/collectives/quantized.py``; ~3.9x less KV HBM); the
  worst-case dequant error is runtime-queryable via
  :meth:`ContinuousBatchingEngine.kv_error_bound`
  (``PT_SERVING_KV_INT8=1`` turns it on when ``kv_int8`` is not passed).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from itertools import islice
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import metrics as _om
from ..utils import faults
from .engine import build_paged_chunk_fn, build_slot_block_fn, init_slot_state

__all__ = ["BlockManager", "PagedArtifactStepBackend",
           "PagedModelStepBackend"]


TRASH_BLOCK = 0

# The chunk program streams every weight once, however few tokens ride
# on the read, so its matmuls are free up to the chip's ridge: peak
# FLOP/s over peak bytes/s = 197e12 / 819e9 = 240 FLOP a byte on a TPU
# v5e, and a bf16 weight gives 2 FLOP a token for its 2 bytes, i.e. 240
# tokens a weight read. 256 is the multiple of the matrix unit's 128
# rows next to that. On the chip (PERF.md section 6, PR 32; a 7B-class
# model's 16 layers, 4,096-token tables) a chunk takes 11.6 ms at 32 and
# at 64 tokens, 13.0 at 128, 22.9 at 256, 42.2 at 512; 128 and 256 serve
# the same tokens a second, 512 pads more than it saves.
PREFILL_CHUNK_RIDGE = 256


def default_prefill_chunk(block_size: int, max_len: int) -> int:
    """The chunk a paged engine prefills in when none is passed: the
    chunk program's ridge (``PREFILL_CHUNK_RIDGE``), never longer than
    the table (``max_len``), in whole KV blocks (at least one)."""
    return max(min(PREFILL_CHUNK_RIDGE, max_len) // block_size, 1) \
        * block_size

# arena metric families (no-ops until metrics.enable()/PT_METRICS)
_M_BLK_FREE = _om.gauge("pt_paging_blocks_free",
                        "arena blocks on the free list")
_M_BLK_REF = _om.gauge("pt_paging_blocks_referenced",
                       "arena blocks held at refcount >= 1")
_M_BLK_CACHED = _om.gauge("pt_paging_blocks_cached",
                          "released registered blocks LRU-retained for "
                          "prefix reuse")
_M_PFX_LOOKUPS = _om.counter("pt_paging_prefix_lookups_total",
                             "prefix-index lookups at admission")
_M_PFX_HITS = _om.counter("pt_paging_prefix_hit_blocks_total",
                          "prompt blocks served from the prefix index")
_M_ALLOC_FAIL = _om.counter("pt_paging_allocate_failures_total",
                            "block allocations refused (pool exhausted "
                            "or injected fault)")
_M_BLK_EVICT = _om.counter("pt_blockmanager_evictions_total",
                           "registered refcount-0 blocks evicted from "
                           "the LRU prefix cache (allocate-pressure "
                           "or fleet watermark)")
_M_BLK_PRESSURE = _om.gauge("pt_blockmanager_block_pressure",
                            "fraction of the usable pool NOT on the "
                            "free list (referenced + LRU-cached) — the "
                            "eviction tier's control signal")


def _sha1_chain(parent_digest: bytes, tokens: Tuple[int, ...]) -> bytes:
    """Rolling block hash: H(parent_digest || token bytes). Chaining
    makes the key position-dependent — block j only matches block j of
    an identical prefix, never a same-content block elsewhere."""
    h = hashlib.sha1(parent_digest)
    h.update(np.asarray(tokens, np.int32).tobytes())
    return h.digest()


def walk_chain(tokens, block_size: int, stop: int, hash_fn=None,
               start: int = 0, parent: bytes = b""):
    """``(digest, tokens)`` of the full blocks ``start .. stop - 1`` of a
    sequence, one at a time as they are asked for; ``parent`` is block
    ``start - 1``'s digest. THE walk over a digest chain: the prefix
    index, the fleet's directory and the spill tier all take it. The
    sequence is touched in bulk — one little-endian int32 copy of the
    blocks walked, one ``tolist()`` for the token tuples the index
    stores and compares, one ``tobytes()`` — and a block costs one sha1
    over ``parent + its 4 * block_size bytes``: byte for byte
    :func:`_sha1_chain`'s digest. A caller's own ``hash_fn`` is called
    a block with ``(parent, tuple)`` instead. A trailing partial block
    is never hashed. Tokens are int32 ids; a wider dtype is cast."""
    bs = block_size
    seq = np.ascontiguousarray(
        np.asarray(tokens)[start * bs:stop * bs], dtype="<i4")
    flat = seq.tolist()
    n = len(flat) // bs
    if hash_fn is None or hash_fn is _sha1_chain:
        raw, width, sha1 = seq.tobytes(), 4 * bs, hashlib.sha1
        for j in range(n):
            parent = sha1(parent + raw[j * width:(j + 1) * width]).digest()
            yield parent, tuple(flat[j * bs:(j + 1) * bs])
    else:
        for j in range(n):
            chunk = tuple(flat[j * bs:(j + 1) * bs])
            parent = hash_fn(parent, chunk)
            yield parent, chunk


class BlockManager:
    """Host-side arena bookkeeping: free list, per-block refcounts,
    rolling-hash prefix index with LRU retention of released registered
    blocks. Pure python — it runs once per admission/retirement, never
    inside the compiled stream, but the device has nothing queued while
    an admission runs, so its cost is idle time. A digest is computed by
    :func:`walk_chain` and counted in ``hashed_blocks``; a caller that
    keeps the chain it was given (:meth:`extend_chain`) has each of a
    sequence's blocks hashed once. A block handed out
    costs O(1) from the free list and O(distinct hit tallies among the
    retained blocks) by eviction — a handful — whatever the number of
    retained blocks: the eviction order is kept as blocks park and
    leave (``_by_hits``), never recomputed by a walk over them."""

    def __init__(self, num_blocks: int, block_size: int, hash_fn=None):
        if num_blocks < 2:
            raise ValueError(f"num_blocks={num_blocks}: need at least "
                             "the trash block plus one usable block")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.hash_fn = hash_fn or _sha1_chain
        self.reset()

    def reset(self):
        self._free: List[int] = list(range(1, self.num_blocks))
        self._ref: Dict[int, int] = {}          # allocated -> refcount
        self._index: Dict[bytes, Tuple[int, Tuple[int, ...]]] = {}
        self._digest_of: Dict[int, bytes] = {}  # registered blocks
        self._depth: Dict[bytes, int] = {}      # digest -> chain blocks
        # retained blocks (registered, refcount 0) in LRU order
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        # block id -> prefix-index hits observed (eviction cost signal)
        self._hits: Dict[int, int] = {}
        # the eviction order: hit tally -> the retained blocks of that
        # tally, LRU first. A tally only moves while its block is held
        # (match_prefix acquires before it counts), so a retained block
        # never changes bucket; an emptied bucket is dropped.
        self._by_hits: Dict[int, "OrderedDict[int, None]"] = {}
        self.lookups = 0
        self.hit_blocks = 0
        self.evictions = 0
        self.hashed_blocks = 0      # digests computed, ever
        self._note_pool()

    def _note_pool(self):
        """Refresh the pool-pressure gauges (one metrics-enabled check;
        called from the host-side accounting paths only)."""
        if not _om.enabled():
            return
        _M_BLK_FREE.set(len(self._free))
        _M_BLK_REF.set(len(self._ref))
        _M_BLK_CACHED.set(len(self._cached))
        _M_BLK_PRESSURE.set(self.block_pressure())

    # -- capacity ----------------------------------------------------------
    def available(self) -> int:
        return len(self._free) + len(self._cached)

    def usable_blocks(self) -> int:
        """Pool capacity excluding the reserved trash block — the
        admission-validation bound (a request needing more than this
        can NEVER be admitted, no matter what retires)."""
        return self.num_blocks - 1

    def block_pressure(self) -> float:
        """Fraction of the usable pool not on the free list. Referenced
        AND LRU-cached blocks both count as pressure: cached blocks are
        reclaimable, but only by evicting warm prefix state — exactly
        the trade the fleet's watermark eviction arbitrates."""
        return 1.0 - len(self._free) / self.usable_blocks()

    def _park(self, block_id: int):
        """Retain a released registered block: youngest of the LRU
        order and of its tally's bucket."""
        self._cached[block_id] = None
        self._by_hits.setdefault(self._hits.get(block_id, 0),
                                 OrderedDict())[block_id] = None

    def _unpark(self, block_id: int):
        """Take a retained block out of the LRU order and its bucket
        (resurrected by a prefix match, or evicted)."""
        del self._cached[block_id]
        tally = self._hits.get(block_id, 0)
        bucket = self._by_hits[tally]
        del bucket[block_id]
        if not bucket:
            del self._by_hits[tally]

    def _reindex_cached(self):
        """Rebuild the eviction order from ``_cached`` (LRU order) and
        ``_hits`` — the two fields a snapshot carries."""
        lru, self._cached, self._by_hits = list(self._cached), \
            OrderedDict(), {}
        for b in lru:
            self._park(b)

    def _next_victim(self) -> int:
        """The retained block to evict next: the head of the lowest
        tally's bucket — no walk over the retained blocks."""
        return next(iter(self._by_hits[min(self._by_hits)]))

    def _evict_victim(self) -> int:
        """Pick and unregister the next cached block to evict. The
        score is COST-AWARE, not pure LRU: least observed prefix-index
        reuse first (a 24-block system prompt shared by 100 tenants
        outlives a cold one-off chain of the same age), ties broken by
        LRU age. With no recorded hits anywhere this degrades to
        exactly the old LRU-first order."""
        best = self._next_victim()
        self._unpark(best)
        digest = self._digest_of.pop(best)
        del self._index[digest]
        self._depth.pop(digest, None)
        self._hits.pop(best, None)
        self.evictions += 1
        _M_BLK_EVICT.inc()
        return best

    def allocate(self, n: int) -> Optional[List[int]]:
        """n fresh blocks at refcount 1, evicting cached prefix blocks
        (least-reused first, then LRU) if the free list runs short;
        None if the pool can't cover the request (caller re-queues).
        The ``serving.allocate`` fault site deterministically simulates
        transient exhaustion (returns None with the pool untouched)."""
        if faults.should_fire("serving.allocate"):
            _M_ALLOC_FAIL.inc()
            return None
        if self.available() < n:
            _M_ALLOC_FAIL.inc()
            return None
        out = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                b = self._evict_victim()
            self._ref[b] = 1
            out.append(b)
        self._note_pool()
        return out

    def eviction_victims(self, n: int) -> List[int]:
        """Non-mutating preview of the next ``n`` blocks
        :meth:`evict_cached` would pick, in eviction order — the spill
        tier reads this to copy exactly the chains about to die,
        WITHOUT perturbing hit counts or LRU order (a perturbed
        preview would desynchronize from the real eviction)."""
        out: List[int] = []
        for tally in sorted(self._by_hits):
            if len(out) >= n:
                break
            out.extend(islice(self._by_hits[tally], n - len(out)))
        return out

    def chain_tokens_map(self) -> Dict[bytes, Tuple[int, ...]]:
        """Reconstruct full chain tokens for every registered digest
        that is reachable from the root: ``{digest: tokens of the
        whole chain ending at it}``. The index stores only per-block
        chunks; this stitches them depth-by-depth by re-deriving each
        digest from its candidate parent — stateless, so the snapshot
        format never changes. A chain whose head was evicted is
        unreachable and simply omitted (it could not be re-matched or
        spilled anyway)."""
        by_depth: Dict[int, List[Tuple[bytes, Tuple[int, ...]]]] = {}
        for d, (_bid, chunk) in self._index.items():
            by_depth.setdefault(self._depth.get(d, 0), []).append(
                (d, chunk))
        toks: Dict[bytes, Tuple[int, ...]] = {}
        for d, chunk in by_depth.get(1, ()):
            if self.hash_fn(b"", chunk) == d:
                toks[d] = tuple(chunk)
        for k in sorted(x for x in by_depth if x > 1):
            prev = [(pd, pt) for pd, pt in toks.items()
                    if self._depth.get(pd) == k - 1]
            for d, chunk in by_depth[k]:
                for pd, pt in prev:
                    if self.hash_fn(pd, chunk) == d:
                        toks[d] = pt + tuple(chunk)
                        break
        return toks

    def evict_cached(self, n: int) -> int:
        """Evict up to ``n`` retained registered blocks back to the
        free list (the fleet's watermark eviction tier drives this),
        least-reused-first with LRU tiebreak (see
        :meth:`_evict_victim`). Referenced blocks are untouchable;
        returns the count actually evicted. Directory consequences are
        the caller's: the owner's next heartbeat publish simply no
        longer lists the digests."""
        done = 0
        while done < n and self._cached:
            self._free.append(self._evict_victim())
            done += 1
        if done:
            self._note_pool()
        return done

    # -- prefix sharing ----------------------------------------------------
    def _shareable_blocks(self, prompt) -> int:
        # whole blocks only, and never the one holding the LAST prompt
        # token — at least one token must prefill so the first-token
        # logits exist
        return (len(prompt) - 1) // self.block_size

    def _walk(self, tokens, stop: int, chain: list):
        """:func:`walk_chain` from where ``chain`` ends to block ``stop``,
        each block counted and appended to ``chain`` as it is hashed."""
        for entry in walk_chain(tokens, self.block_size, stop, self.hash_fn,
                                len(chain), chain[-1][0] if chain else b""):
            self.hashed_blocks += 1
            chain.append(entry)
            yield entry

    def find_prefix(self, prompt, chain: Optional[list] = None
                    ) -> List[Tuple[bytes, Tuple[int, ...], int]]:
        """Longest chain of indexed blocks matching the prompt's full
        prefix blocks, as ``(digest, tokens, block)`` a block; nothing is
        acquired. A digest hit whose stored tokens differ (hash
        collision) stops the chain — the caller just recomputes from
        there — and nothing past the first miss is hashed. ``chain``, an
        empty list, receives ``(digest, tokens)`` of every block hashed
        (the matches and the block the match stopped at) for the caller
        to carry: :meth:`extend_chain`."""
        self.lookups += 1
        _M_PFX_LOOKUPS.inc()
        found = []
        for digest, chunk in self._walk(
                prompt, self._shareable_blocks(prompt),
                [] if chain is None else chain):
            entry = self._index.get(digest)
            if entry is None or entry[1] != chunk:
                break
            found.append((digest, chunk, entry[0]))
        return found

    def block_of(self, digest: bytes, chunk) -> Optional[int]:
        """The indexed block holding chain position ``digest`` (its
        tokens ``chunk``), or None."""
        entry = self._index.get(digest)
        return entry[0] if entry is not None and entry[1] == chunk else None

    def acquire_hits(self, blocks: Sequence[int]):
        """Ref-acquire matched blocks for the caller and count the hits."""
        for b in blocks:
            self._acquire(b)
            # reuse tally: the eviction tier's cost signal — every
            # observed hit makes the block costlier to evict
            self._hits[b] = self._hits.get(b, 0) + 1
        self.hit_blocks += len(blocks)
        _M_PFX_HITS.inc(len(blocks))
        self._note_pool()

    def match_prefix(self, prompt, chain: Optional[list] = None
                     ) -> List[int]:
        """:meth:`find_prefix`, each match ref-acquired for the caller."""
        blocks = [b for _, _, b in self.find_prefix(prompt, chain)]
        self.acquire_hits(blocks)
        return blocks

    def _acquire(self, block_id: int):
        r = self._ref.get(block_id, 0)
        if r == 0:                    # resurrect from the LRU cache
            self._unpark(block_id)
        self._ref[block_id] = r + 1

    def extend_chain(self, chain: list, tokens, n_blocks: int) -> list:
        """Extend ``chain`` — ``(digest, tokens)`` of the first
        ``len(chain)`` full blocks of ``tokens`` — in place to
        ``n_blocks``, hashing only the blocks it lacks, and return it."""
        for _ in self._walk(tokens, n_blocks, chain):
            pass
        return chain

    def chain(self, tokens, n_blocks: int) -> List[Tuple[bytes,
                                                          Tuple[int, ...]]]:
        """``(digest, tokens)`` of the first ``n_blocks`` full blocks."""
        return self.extend_chain([], tokens, n_blocks)

    def register_chain(self, chain, block_ids: Sequence[int],
                       first: int = 0):
        """Index chain positions ``first ..`` (now filled) under
        ``block_ids[j]``, so later requests can share them. A position
        already indexed, or a block already registered, is a no-op. A
        pool that keeps only a prefix's TAIL registers from ``first`` on."""
        for j in range(first, len(chain)):
            digest, chunk = chain[j]
            bid = block_ids[j]
            if digest not in self._index and bid not in self._digest_of:
                self._index[digest] = (bid, chunk)
                self._digest_of[bid] = digest
            # depth is a pure function of the digest (it hashes the
            # whole chain), so re-registration writes the same value
            if digest in self._index:
                self._depth[digest] = j + 1

    def register_prefix(self, prompt, block_ids: Sequence[int],
                        n_blocks: Optional[int] = None):
        """Index the prompt's full prefix blocks (now filled) so later
        requests can share them. Blocks that were themselves matched
        from the index re-derive the same digests — no-ops.

        ``n_blocks`` overrides the default shareable count — decode-time
        block sharing passes the FULLY-WRITTEN block count of the
        completed sequence (every position resident, including decode
        positions), which can exceed ``_shareable_blocks`` of the prompt
        alone."""
        if n_blocks is None:
            n_blocks = self._shareable_blocks(prompt)
        self.register_chain(self.chain(prompt, n_blocks), block_ids)

    def registered_chains(self) -> Dict[bytes, int]:
        """``{digest: covered_blocks}`` for every registered block —
        what a fleet worker publishes to the prefix-cache directory on
        each heartbeat. A digest at chain position j covers j+1 blocks
        of any prompt whose prefix hashes to it."""
        return {d: self._depth.get(d, 0) for d in self._index}

    def release(self, block_ids: Sequence[int]):
        """Drop one reference per block. At refcount 0 a registered
        block parks in the LRU cache (still matchable); an unregistered
        one returns to the free list. Releasing an unheld block is a
        hard error — the double-free guard."""
        for bid in block_ids:
            r = self._ref.get(bid)
            if not r:
                raise RuntimeError(f"double free of arena block {bid}")
            if r > 1:
                self._ref[bid] = r - 1
            else:
                del self._ref[bid]
                if bid in self._digest_of:
                    self._park(bid)
                else:
                    self._free.append(bid)
        self._note_pool()

    # -- invariants --------------------------------------------------------
    def assert_consistent(self):
        """Hard-check the arena accounting invariants (paging test
        teardowns + the chaos suite call this after every stream):

        - free + referenced + LRU-retained partition the usable pool
          exactly (every non-trash block in exactly ONE set);
        - every refcount >= 1 (zeroes must leave the map);
        - the prefix index and the registered-block map are mutual
          inverses, retained blocks are all registered, and no free
          block is still registered;
        - the eviction order holds exactly the retained blocks, each
          under its current hit tally, in LRU order within a tally.
        """
        free, ref = set(self._free), set(self._ref)
        cached, reg = set(self._cached), set(self._digest_of)
        assert len(self._free) == len(free), \
            f"duplicate ids in free list: {sorted(self._free)}"
        assert not (free & ref), f"free AND referenced: {free & ref}"
        assert not (free & cached), f"free AND retained: {free & cached}"
        assert not (ref & cached), \
            f"referenced AND retained: {ref & cached}"
        universe = free | ref | cached
        assert TRASH_BLOCK not in universe, "trash block was allocated"
        want = set(range(1, self.num_blocks))
        assert universe == want, (
            f"block accounting leak: missing {sorted(want - universe)}, "
            f"unknown {sorted(universe - want)}")
        bad_refs = {b: r for b, r in self._ref.items() if r < 1}
        assert not bad_refs, f"non-positive refcounts: {bad_refs}"
        assert cached <= reg, \
            f"retained but unregistered: {cached - reg}"
        assert not (free & reg), \
            f"free but still registered: {free & reg}"
        assert len(self._index) == len(reg), \
            "prefix index and registered-block map out of sync"
        for digest, (bid, _) in self._index.items():
            assert self._digest_of.get(bid) == digest, \
                f"index entry for block {bid} disagrees with digest map"
        stale_depth = set(self._depth) - set(self._index)
        assert not stale_depth, \
            f"chain-depth entries for unregistered digests: " \
            f"{sorted(d.hex() for d in stale_depth)}"
        stale_hits = set(self._hits) - reg
        assert not stale_hits, \
            f"reuse tallies for unregistered blocks: " \
            f"{sorted(stale_hits)}"
        want_order: Dict[int, List[int]] = {}
        for b in self._cached:
            want_order.setdefault(self._hits.get(b, 0), []).append(b)
        have_order = {t: list(bk) for t, bk in self._by_hits.items()}
        assert have_order == want_order, (
            f"eviction order out of step with the retained blocks: "
            f"have {have_order}, want {want_order}")


def refuse_looped_cache(holder, what: str):
    """What moves or re-shapes ONE block of ``num_blocks`` (a hand-off, the
    fleet's prefix tier, a sharded or verify-capable or exported backend)
    cannot hold a looped model's cache yet, where a block id stands for
    ``kv_cache_passes`` pages of every arena: refuse by name. ``holder`` is
    the model (``kv_cache_passes``) or an engine built on it
    (``cache_passes``)."""
    passes = int(getattr(holder, "kv_cache_passes",
                         getattr(holder, "cache_passes", 1)))
    if passes > 1:
        raise NotImplementedError(
            f"{what} over a looped cache (kv_cache_passes = {passes}): a "
            f"block id stands for {passes} pages of every arena there, "
            "one a pass, and this path moves or shapes only one")


def refuse_conv_state(holder, what: str):
    """What cannot carry a model's conv state yet (a leaf indexed by the
    block ids beside the arenas: ``conv_state_layers``, e.g.
    ``models.lfm2_moe``) refuses by name. ``holder`` is the model or a
    backend built on it."""
    layers = int(getattr(holder, "conv_state_layers", 0))
    if layers:
        raise NotImplementedError(
            f"{what} over a conv state ({layers} conv layers keep theirs a "
            "block at a time beside the arenas): this path has no rule for "
            "it yet")


class _StepBackendCommon:
    """What the in-process and the exported step backends share: the
    slot state with its block tables, a zeroed arena, and the byte
    counts — keyed off ``num_slots``/``max_blocks``/``pool_specs``,
    which each backend sets up."""

    # weight-only quantization state (serving/quant.py): None/empty on
    # fp32 backends, so every hot path stays one falsy check
    quant_cfg = None
    _qmeta = None
    _weight_bound = 0.0

    # columns of a slot's table row (one table of ``max_blocks``)
    table_width = property(lambda self: self.max_blocks)

    def init_state(self):
        state = init_slot_state(self.num_slots)
        state["table"] = jnp.zeros((self.num_slots, self.table_width),
                                   jnp.int32)        # all-trash tables
        return state

    def pool_cache(self):
        return tuple(jnp.zeros(shape, dtype)
                     for shape, dtype in self.pool_specs)

    def kv_bytes_per_slot(self) -> int:
        """HBM bytes of KV cache per slot (the arena is shared, so the
        per-slot figure shrinks with block count)."""
        total = sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
                    for shape, dtype in self.pool_specs)
        return total // self.num_slots

    def param_bytes(self) -> int:
        """HBM bytes of weights + buffers one decode step reads (codes
        AND scales under weight-only quant — the wire footprint, which
        is the point)."""
        return sum(int(v.nbytes) for v in jax.tree.leaves(self._pv)) \
            + sum(int(v.nbytes) for v in jax.tree.leaves(self._bv))


class PagedModelStepBackend(_StepBackendCommon):
    """In-process backend over a live model: the pool cache is the shared
    block arena, the decode program threads the in-state block table
    through the forward (the same pure step ``generate()`` uses), and
    prefill is ONE fixed-shape chunk program."""

    def __init__(self, model, num_slots: int, max_len: int,
                 decode_block: int, block_size: int, num_blocks: int,
                 kv_int8: bool, prefill_chunk: int, quant=None):
        from ..models.generation import (build_decode_step,
                                         forward_accepts_block_table,
                                         forward_accepts_pad)
        from ..tensor import Tensor
        if not forward_accepts_pad(type(model)):
            raise ValueError(
                f"{type(model).__name__}.forward does not accept per-row "
                "pad counts — the slot pool needs ragged decode support")
        if not forward_accepts_block_table(type(model)):
            raise ValueError(
                f"{type(model).__name__}.forward does not accept a "
                "block_table — paged KV needs it threaded to "
                "cached_attention (see models/llama.py)")
        if max_len % block_size != 0:
            raise ValueError(f"max_len={max_len} must be a multiple of "
                             f"block_size={block_size}")
        self.num_slots, self.max_len = num_slots, max_len
        self.block_size = decode_block
        self.kv_block_size = block_size
        self.num_kv_blocks = num_blocks
        self.max_blocks = max_len // block_size
        self.kv_int8 = kv_int8
        self.prefill_chunk_len = prefill_chunk
        tree_holder = {"tree": None}
        self._tree_holder = tree_holder    # spec backends reuse it
        self._pure = build_decode_step(model, None, tree_holder)
        cache0 = self._new_cache(model)
        flat, tree = jax.tree.flatten(
            cache0, is_leaf=lambda x: isinstance(x, Tensor))
        tree_holder["tree"] = tree
        self.pool_specs = tuple((c._value.shape, c._value.dtype)
                                for c in flat)
        # a model whose programs count (routed picks, experts hit) keeps
        # the counts in the cache's LAST leaf and names them here
        self.cache_counters = dict(getattr(model, "cache_counters", {}))
        # a looped model runs each weight layer ``kv_cache_passes`` times a
        # step, each pass over a slice of the layer's arenas of its own
        self.cache_passes = int(getattr(model, "kv_cache_passes", 1))
        # layers whose state the cache carries a block at a time beside
        # the arenas (their own leaf, under the same block ids)
        self.conv_state_layers = int(getattr(model, "conv_state_layers", 0))
        self.attn_sites = self.cache_passes * int(getattr(
            getattr(model, "config", None), "num_hidden_layers", 0))
        self._pv = [p._value for _, p in model.named_parameters()]
        self._bv = [b._value for _, b in model.named_buffers()]
        # weight-only quant BEFORE the decode-block and chunk programs
        # are built (serving/quant.py)
        self._setup_weight_quant(model, quant)
        self._pure = self._maybe_quant_pure(self._pure)
        self.decode_traces = [0]
        self.prefill_traces = [0]
        self._block_jit = jax.jit(
            build_slot_block_fn(self._pure, decode_block,
                                self.decode_traces),
            donate_argnums=(2, 3))
        self._chunk_jit = jax.jit(
            build_paged_chunk_fn(self._pure, prefill_chunk,
                                 self.prefill_traces),
            donate_argnums=(3,))

    def _new_cache(self, model):
        return model.init_paged_kv_cache(self.num_kv_blocks,
                                         self.kv_block_size,
                                         kv_int8=self.kv_int8)

    def _setup_weight_quant(self, model, quant):
        """Quantize the serving weight set in-place (between pv
        construction and program building; see serving/quant.py).
        No-op when ``quant`` is None."""
        if quant is None:
            return
        from .quant import quantize_backend_params
        self.quant_cfg = quant
        self._pv, self._qmeta, self._weight_bound = \
            quantize_backend_params(model, self._pv, quant)

    def _maybe_quant_pure(self, pure):
        """Wrap a pure step with the in-graph dequant when this backend
        holds quantized weights — EVERY program (decode block, chunk,
        spec verify) must be built from the wrapped step."""
        if not self._qmeta:
            return pure
        from .quant import wrap_pure_with_dequant
        return wrap_pure_with_dequant(pure, self._qmeta)

    def decode_block(self, cache_flat, state):
        return self._block_jit(self._pv, self._bv, cache_flat, state)

    def prefill_chunk(self, ids, cache_flat, table_row, start_pos,
                      n_valid, key, temp, topk, topp):
        return self._chunk_jit(self._pv, self._bv, ids, cache_flat,
                               table_row, start_pos, n_valid, key, temp,
                               topk, topp)


def artifact_fingerprint(cfgs: dict, *programs: bytes) -> str:
    """Artifact identity: sha1 over the recorded config + the
    serialized programs. Recorded into engine snapshots so a restore
    onto a DIFFERENT artifact is refused."""
    h = hashlib.sha1(repr(sorted(
        (k, str(v)) for k, v in cfgs.items())).encode())
    for prog in programs:
        h.update(prog)
    return h.hexdigest()


class PagedArtifactStepBackend(_StepBackendCommon):
    """AOT backend: the engine's TWO programs (ONE decode block + ONE
    chunked-prefill chunk), deserialized from an
    ``export_decoder(..., engine_slots=N)`` artifact — no model code or
    tracing needed on the serving host (reference: AnalysisPredictor
    serving from the saved program alone). The
    ``artifact_fingerprint`` (sha1 over the serialized programs +
    config) rides engine snapshots so a restore onto a DIFFERENT
    artifact is refused instead of silently resuming on other
    programs."""

    def __init__(self, blob):
        eng = blob["engine"]
        cfgs = eng["config"]
        if not cfgs.get("paged"):
            raise ValueError(
                "artifact holds the removed dense slot-pool engine's "
                "programs — re-export it with export_decoder(..., "
                "engine_slots=N)")
        self.artifact_fingerprint = artifact_fingerprint(
            cfgs, eng["block"], eng["chunk"])
        self.num_slots = cfgs["num_slots"]
        self.max_len = cfgs["max_len"]
        self.block_size = cfgs["decode_block"]
        self.kv_block_size = cfgs["block_size"]
        self.num_kv_blocks = cfgs["num_blocks"]
        self.max_blocks = self.max_len // self.kv_block_size
        self.kv_int8 = bool(cfgs.get("kv_int8", False))
        self.prefill_chunk_len = cfgs["prefill_chunk"]
        self.pool_specs = tuple((tuple(shape), np.dtype(dtype))
                                for shape, dtype in eng["pool_specs"])
        self._block = jax.export.deserialize(eng["block"])
        self._chunk = jax.export.deserialize(eng["chunk"])
        self._pv = [jnp.asarray(v) for v in blob["params"]]
        self._bv = [jnp.asarray(v) for v in blob["buffers"]]
        self.decode_traces = [1]     # two AOT-compiled programs
        self.prefill_traces = [1]

    def decode_block(self, cache_flat, state):
        return self._block.call(self._pv, self._bv, cache_flat, state)

    def prefill_chunk(self, ids, cache_flat, table_row, start_pos,
                      n_valid, key, temp, topk, topp):
        return self._chunk.call(self._pv, self._bv, ids, cache_flat,
                                table_row, start_pos, n_valid, key,
                                temp, topk, topp)
