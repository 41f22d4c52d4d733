"""Block-paged KV cache for the continuous-batching engine: shared
arena + per-slot block tables, ref-counted prefix reuse, chunked
prefill.

The dense engine (engine.py) preallocates one ``(max_len, kvh, d)`` KV
row per slot, so HBM is sized for the worst-case sequence and a shared
system prompt is recomputed and stored per request. Paged mode replaces
the per-slot rows with ONE ``(num_blocks, block_size, kvh, d)`` arena
per layer plus an in-graph ``(S, max_blocks)`` block table riding the
slot state (vLLM's PagedAttention restated under the repo's
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
  ``budget`` tokens of prefill per tick. The dense engine's per-bucket
  prefill jits collapse to one program.
- Attention runs the Pallas paged-attention kernel on TPU and the
  gathered-dense reference off-TPU (ops/pallas/paged_attention.py);
  greedy paged streams are bit-identical to the dense fp32 engine and
  to per-request ``generate()``.
- ``kv_int8=True`` stores the arena as int8 codes + per-vector fp32
  absmax scales (the EQuARX recipe from
  ``distributed/collectives/quantized.py``; ~3.9x less KV HBM); the
  worst-case dequant error is runtime-queryable via
  :meth:`PagedEngine.kv_error_bound`.

Everything is default-off: construct ``ContinuousBatchingEngine(...,
paged=True)`` or set ``PT_SERVING_PAGED=1`` (``PT_SERVING_KV_INT8=1``
for the int8 arena).
"""
from __future__ import annotations

import hashlib
import time
from collections import OrderedDict
from dataclasses import dataclass
from itertools import islice
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import metrics as _om
from ..observability.tracing import now_us as _trace_now, span as _span
from ..utils import faults
from ..utils.flags import env_flag, env_int
from .engine import (ContinuousBatchingEngine, ModelStepBackend, _SlotRun,
                     _M_PREFILLS, _M_TOKENS, _StepBackendCommon,
                     artifact_fingerprint, build_paged_chunk_fn,
                     build_slot_block_fn, init_slot_state)

__all__ = ["BlockManager", "PagedArtifactStepBackend",
           "PagedModelStepBackend", "PagedEngine"]

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


class PagedModelStepBackend(ModelStepBackend):
    """Paged twin of ModelStepBackend: the pool cache is the shared
    block arena, the decode program threads the in-state block table
    through the forward, and prefill is ONE fixed-shape chunk program
    instead of per-bucket jits."""

    is_paged = True      # engine.__new__ routes on this, not isinstance

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
                                self.decode_traces, paged=True),
            donate_argnums=(2, 3))
        self._chunk_jit = jax.jit(
            build_paged_chunk_fn(self._pure, prefill_chunk,
                                 self.prefill_traces),
            donate_argnums=(3,))

    def _new_cache(self, model):
        return model.init_paged_kv_cache(self.num_kv_blocks,
                                         self.kv_block_size,
                                         kv_int8=self.kv_int8)

    # columns of a slot's table row (one table of ``max_blocks``)
    table_width = property(lambda self: self.max_blocks)

    def init_state(self):
        state = init_slot_state(self.num_slots)
        state["table"] = jnp.zeros((self.num_slots, self.table_width),
                                   jnp.int32)        # all-trash tables
        return state

    def prefill_chunk(self, ids, cache_flat, table_row, start_pos,
                      n_valid, key, temp, topk, topp):
        return self._chunk_jit(self._pv, self._bv, ids, cache_flat,
                               table_row, start_pos, n_valid, key, temp,
                               topk, topp)

    def prefill(self, *a, **kw):
        raise RuntimeError("the paged backend prefills in chunks — use "
                           "prefill_chunk (engine.admit drives it)")


class PagedArtifactStepBackend(_StepBackendCommon):
    """AOT paged backend: the paged engine's TWO programs (ONE decode
    block + ONE chunked-prefill chunk), deserialized from an
    ``export_decoder(..., engine_slots=N, engine_paged=True)`` artifact
    — no model code or tracing needed on the serving host. The
    ``artifact_fingerprint`` (sha1 over the serialized programs +
    config) rides engine snapshots so a restore onto a DIFFERENT
    artifact is refused instead of silently resuming on other
    programs."""

    is_paged = True

    def __init__(self, blob):
        eng = blob["engine"]
        cfgs = eng["config"]
        if not cfgs.get("paged"):
            raise ValueError(
                "artifact holds the dense engine programs — load it "
                "with ArtifactStepBackend, or re-export with "
                "export_decoder(..., engine_paged=True)")
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
        self.carries_nan_flags = cfgs.get("block_outputs", 4) >= 5
        self.pool_specs = tuple((tuple(shape), np.dtype(dtype))
                                for shape, dtype in eng["pool_specs"])
        self._block = jax.export.deserialize(eng["block"])
        self._chunk = jax.export.deserialize(eng["chunk"])
        self._pv = [jnp.asarray(v) for v in blob["params"]]
        self._bv = [jnp.asarray(v) for v in blob["buffers"]]
        self.decode_traces = [1]     # two AOT-compiled programs
        self.prefill_traces = [1]

    def init_state(self):
        state = init_slot_state(self.num_slots)
        state["table"] = jnp.zeros((self.num_slots, self.max_blocks),
                                   jnp.int32)
        return state

    def pool_cache(self):
        return tuple(jnp.zeros(shape, dtype)
                     for shape, dtype in self.pool_specs)

    def decode_block(self, cache_flat, state):
        return self._block.call(self._pv, self._bv, cache_flat, state)

    def prefill_chunk(self, ids, cache_flat, table_row, start_pos,
                      n_valid, key, temp, topk, topp):
        return self._chunk.call(self._pv, self._bv, ids, cache_flat,
                                table_row, start_pos, n_valid, key,
                                temp, topk, topp)

    def prefill(self, *a, **kw):
        raise RuntimeError("the paged backend prefills in chunks — use "
                           "prefill_chunk (engine.admit drives it)")


def _arm_fn(state, slot, table_row, tok0, pos0, rem0, eos0, temp0,
            topk0, topp0, key0):
    """Turn a slot live after its chunked prefill finished: the arena
    already holds the prompt's K/V, so arming is a pure state update
    (the paged analogue of engine._admit_fn without the row splice).
    ``slot`` is traced — one compiled program serves every arming."""

    def set1(a, v):
        return a.at[slot].set(jnp.asarray(v, a.dtype))

    return dict(
        state, tok=set1(state["tok"], tok0),
        pos=set1(state["pos"], pos0),
        pad=set1(state["pad"], 0),        # paged prompts are unpadded
        live=set1(state["live"], rem0 > 0),
        eos=set1(state["eos"], eos0),
        remaining=set1(state["remaining"], rem0),
        key=state["key"].at[slot].set(key0),
        temp=set1(state["temp"], temp0),
        topk=set1(state["topk"], topk0),
        topp=set1(state["topp"], topp0),
        table=state["table"].at[slot].set(table_row))


@dataclass
class _PrefillJob:
    """One admitted request still streaming its prompt into the arena
    (``done`` counts tokens already resident, including the shared
    prefix it skipped). For a preemption resume, ``prompt`` is the
    original prompt plus the generated history being re-prefilled and
    ``resume_tok`` is the carried in-hand next token — the chunk
    programs' in-graph samples are discarded and the slot arms with it
    instead."""
    run: _SlotRun
    slot: int
    prompt: np.ndarray
    done: int
    table_row: np.ndarray          # (max_blocks,) int32
    key: jnp.ndarray               # post-split state key
    sub: jnp.ndarray               # prefill sampling key
    temp: jnp.ndarray
    topk: jnp.ndarray
    topp: jnp.ndarray
    tok0: Optional[int] = None
    resume_tok: Optional[int] = None


@dataclass
class _Reservation:
    """What an admission holds once its blocks are reserved: the run's
    blocks (shared prefix first), its table row, how many leading prompt
    blocks were served from the prefix index, what the ``serving.admit``
    span says of it, and the digests the lookup computed (``chain``, for
    the run to carry). An engine with a second pool adds what the run
    holds there (``window``)."""
    block_ids: List[int]
    table_row: np.ndarray
    shared_blocks: int
    span_ids: dict
    chain: list
    window: Optional[object] = None


class PagedEngine(ContinuousBatchingEngine):
    """Paged-KV continuous batching. Same Server/Scheduler contract as
    the dense engine; differences:

    - ``admit()`` only reserves blocks and queues a prefill job; the
      prompt streams into the arena via :meth:`prefill_tick` (chunk
      programs), and the slot arms when its last chunk lands.
    - ``try_admit()`` can return False (block pool exhausted) — the
      Server re-queues and retries after retirements free blocks.
    - prompts are UNPADDED (no buckets): position 0 is token 0, which
      is what makes whole prefix blocks shareable across requests.
    - ``prefill_chunk`` (else ``PT_SERVING_PREFILL_CHUNK``) is the chunk
      program's length in tokens; when neither is given it is
      :func:`default_prefill_chunk` — the program's ridge, where a
      chunk's matmuls take as long as the weight read every chunk pays,
      so a prompt re-reads the weights once per ~256 tokens.
      :meth:`chunk_fill_share` says how much of the chunks was prompt.
    """

    def __init__(self, model=None, num_slots: int = 4,
                 max_len: int = 256, decode_block: int = 8,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 backend=None, *, paged: bool = True, spec=None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 kv_int8: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 hash_fn=None, tp=None, quant=None):
        if prompt_buckets is not None:
            raise ValueError(
                "paged mode takes no prompt_buckets: prompts are "
                "unpadded and prefilled in fixed-size chunks")
        if backend is not None:
            # the backend already baked these in — a silently ignored
            # kv_int8=True (fp32 arena, bound 0.0), num_blocks or
            # quant= would be a misconfiguration, not a preference
            given = {k: v for k, v in (("block_size", block_size),
                                       ("num_blocks", num_blocks),
                                       ("kv_int8", kv_int8),
                                       ("prefill_chunk", prefill_chunk),
                                       ("quant", quant))
                     if v is not None}
            if given:
                raise ValueError(
                    f"{sorted(given)} cannot be set alongside an "
                    "explicit backend — they are baked into it at "
                    "construction")
        if block_size is None:
            # resolution order: explicit arg > env knob > a valid
            # (stamp-matching) autotune-table winner > the documented
            # default 16 — a stale table never silently reshapes arenas
            block_size = env_int("PT_SERVING_BLOCK_SIZE", 0)
            if block_size <= 0:
                from ..ops.pallas.autotune import tuned_paged_block_size
                block_size = tuned_paged_block_size(16)
        if num_blocks is None:
            # full dense capacity + trash by default — HBM savings come
            # from passing a smaller pool (plus sharing); correctness
            # never depends on the pool being oversized
            num_blocks = 1 + num_slots * (max_len // block_size)
        if kv_int8 is None:
            kv_int8 = env_flag("PT_SERVING_KV_INT8")
        if prefill_chunk is None:
            prefill_chunk = env_int(
                "PT_SERVING_PREFILL_CHUNK",
                default_prefill_chunk(block_size, max_len))
        if backend is None:
            if model is None:
                raise ValueError("pass a model or a paged step backend")
            from .quant import resolve_quant_config
            from .tp import resolve_tp_config
            tp_cfg = resolve_tp_config(tp)
            q_cfg = resolve_quant_config(quant)
            if tp_cfg is not None:
                # tensor-parallel paged serving: the shared KV arena
                # shards its kv-head dim over the mesh (serving/tp.py);
                # an explicit backend is never rerouted by the env flag
                from .tp import ShardedPagedStepBackend
                backend = ShardedPagedStepBackend(
                    model, num_slots, max_len, decode_block,
                    block_size, num_blocks, bool(kv_int8),
                    prefill_chunk, tp_cfg, quant=q_cfg)
            else:
                # subclass hook: the speculative engine swaps in the
                # verify-capable paged backend here (serving/spec.py)
                backend = self._build_paged_backend(
                    model, num_slots, max_len, decode_block, block_size,
                    num_blocks, bool(kv_int8), prefill_chunk, q_cfg)
        self.kv_block_size = backend.kv_block_size
        self.num_kv_blocks = backend.num_kv_blocks
        self.max_blocks = backend.max_blocks
        self.kv_int8 = backend.kv_int8
        self.prefill_chunk_len = backend.prefill_chunk_len
        # passes a step makes over the layers (1 unless the model loops)
        # and the cached-attention sites a decode step holds: the page
        # counters below stay ONE site's
        self.cache_passes = getattr(backend, "cache_passes", 1)
        self.attn_sites = getattr(backend, "attn_sites", 0)
        self.manager = BlockManager(self.num_kv_blocks,
                                    self.kv_block_size, hash_fn)
        self._arm_jit = jax.jit(_arm_fn, donate_argnums=(0,))
        super().__init__(backend=backend, spec=spec)

    def _build_paged_backend(self, model, num_slots, max_len,
                             decode_block, block_size, num_blocks,
                             kv_int8, prefill_chunk, quant=None):
        return PagedModelStepBackend(
            model, num_slots, max_len, decode_block, block_size,
            num_blocks, kv_int8, prefill_chunk, quant=quant)

    # -- lifecycle ---------------------------------------------------------
    def reset(self):
        super().reset()
        self.manager.reset()
        self._jobs: List[_PrefillJob] = []
        self.prompt_tokens = 0         # all prompt tokens submitted
        self.shared_tokens = 0         # skipped via prefix reuse
        self.prefilled_tokens = 0      # actually computed
        self.prefill_chunks = 0        # chunk programs dispatched
        self.fetched_tokens = 0        # of shared: remote-fetched KV
        # the decode kernel's walk (per attention layer), and the host's
        # mirror of the in-graph per-slot ``pos`` it is counted from
        self.kv_pages_live = 0         # pages holding a slot's live KV
        self.kv_pages_copied = 0       # pages the kernel copied for them
        self.kv_pages_in_runs = 0      # of the live, pages moved in runs
        # each slot's table row as the walk's run copies see it
        # (``paged_attention.run_groups``), from the row it was armed with
        self._run_groups = self._table_run_groups(
            np.zeros((self.num_slots, self.max_blocks), np.int32))
        self.ut_steps = 0              # looped model: passes run by decode
        self._pos_host = np.zeros((self.num_slots,), np.int64)
        # what the model's programs count into the cache's last leaf
        # (``cache_counters``: name -> "sum" | "max"; a (2, n) int32 array,
        # decode steps apart from prefill chunks): the decode row becomes
        # an engine counter of that name, the chunk row ``prefill_<name>``
        self._counter_names = tuple(
            getattr(self.backend, "cache_counters", {}).items())
        self._counts_seen = np.zeros((2, len(self._counter_names)),
                                     np.int64)
        for name, _ in self._counter_names:
            setattr(self, name, 0)
            setattr(self, "prefill_" + name, 0)
        self._chunk_span = None        # the latest ``serving.prefill_chunk``
        self._chunks_unread = 0        # chunks since the counters were read
        self._arm_ns = 0               # ends of prefill since the last block

    # -- introspection -----------------------------------------------------
    def prefix_cache_hit_rate(self) -> float:
        """Fraction of submitted prompt tokens served from shared
        prefix blocks instead of recomputed."""
        return self.shared_tokens / self.prompt_tokens \
            if self.prompt_tokens else 0.0

    def chunk_fill_share(self) -> float:
        """Fraction of the chunk programs' columns that held a prompt
        token (the rest was right-padding): a longer chunk re-reads the
        weights less often and pads more."""
        columns = self.prefill_chunks * self.prefill_chunk_len
        return self.prefilled_tokens / columns if columns else 0.0

    def prefill_compile_count(self) -> int:
        return self.backend.prefill_traces[0]

    def kv_error_bound(self) -> float:
        """Runtime worst-case |dequantized - fp32| over the int8 arena
        (0.0 in fp32 mode): the EQuARX single-quantization bound from
        the largest live absmax scale."""
        if not self.kv_int8:
            return 0.0
        from ..ops.pallas.paged_attention import kv_int8_error_bound
        worst = 0.0
        for (shape, dtype), buf in zip(self.backend.pool_specs,
                                       self._cache):
            if np.dtype(dtype) == np.float32 and len(shape) == 3:
                worst = max(worst, float(jnp.max(buf)))
        return float(kv_int8_error_bound(worst))

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        # positions written: prompt [0, L) plus generated tokens at
        # [L, L+max_new-1) — the final sampled token is never written
        return -(-(prompt_len + max(max_new_tokens - 1, 0))
                 // self.kv_block_size)

    def bucket_len(self, prompt_len: int) -> int:
        return prompt_len            # unpadded prompts, no buckets

    def validate_request(self, prompt_len: int, max_new_tokens: int):
        super().validate_request(prompt_len, max_new_tokens)
        need = self.blocks_needed(prompt_len, max_new_tokens)
        # the MANAGER is the source of truth, not the engine's
        # num_kv_blocks attribute: allocate() draws from the manager,
        # so validating against a stale attribute let an impossible
        # request through the door and into run_until_idle's re-queue
        # path forever (the PR-5 livelock fix; regression-pinned with a
        # tiny pool in tests/test_resilience.py)
        pool = self.manager.usable_blocks()
        if need > pool:
            raise ValueError(
                f"request needs {need} KV blocks but the arena only "
                f"has {pool}; raise num_blocks or shorten the request")

    # -- admission ---------------------------------------------------------
    def try_admit(self, request) -> bool:
        """Block allocation, prefix lookup and slot arming for one
        request (the chunks themselves run in :meth:`prefill_tick`);
        False when the block pool cannot hold it yet. The span says how
        many blocks the admission allocated (``fresh_blocks``) and how
        many of those it took by eviction (``evicted_blocks``), and how
        many block digests it computed (``hashed_blocks``); an admission
        that went through also marks where its time went: ``reserved_ns``
        when the lookup, the allocation and the table row were done,
        ``keyed_ns`` when the request's key was made and split; the rest
        to the span's end is the sampling scalars' uploads and the job."""
        with _span("serving.admit", rid=request.request_id) as sp:
            m = self.manager
            evicted, hashed = m.evictions, m.hashed_blocks
            ids = self._try_admit(request, sp)
            sp.ids.update(
                ids or {"fresh_blocks": 0},
                evicted_blocks=m.evictions - evicted,
                hashed_blocks=m.hashed_blocks - hashed)
            return ids is not None

    def _reserve(self, full, mnt) -> Optional[_Reservation]:
        """Blocks and table row for a request of prompt ``full`` and
        ``mnt`` new tokens: the matched prefix's blocks shared, the rest
        fresh; None when the pool cannot cover it yet (nothing held)."""
        chain = []
        shared = self._match_prefix_for_admission(full, chain)
        total = self.blocks_needed(len(full), mnt)
        fresh = self.manager.allocate(total - len(shared))
        if fresh is None:            # pool exhausted: retry later
            self.manager.release(shared)
            return None
        block_ids = shared + fresh
        table_row = np.zeros((self.max_blocks,), np.int32)
        table_row[:len(block_ids)] = block_ids
        return _Reservation(block_ids, table_row, len(shared),
                            {"fresh_blocks": len(fresh)}, chain)

    def _try_admit(self, request, sp) -> Optional[dict]:
        """The admission proper, marking its span ``sp``: what the span
        says of the blocks it allocated, or None when a pool cannot hold
        the request yet."""
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        resume = getattr(request, "resume", None)
        if resume is not None and resume.tokens:
            # preemption resume: the "prompt" to prefill is the original
            # prompt plus the generated history minus the in-hand next
            # token; the first full prompt blocks are usually still in
            # the prefix index (eviction retained them), so most of this
            # re-prefill is cache hits rather than recompute
            full = np.concatenate([
                prompt, np.asarray(resume.tokens[:-1], np.int32)])
            mnt = request.max_new_tokens - len(resume.tokens) + 1
        else:
            resume = None
            full, mnt = prompt, request.max_new_tokens
        L = int(full.shape[0])
        self.validate_request(L, mnt)
        slot = next((i for i, s in enumerate(self._slots) if s is None),
                    None)
        if slot is None:
            raise RuntimeError("no free slot (scheduler bug)")
        held = self._reserve(full, mnt)
        if held is None:
            return None
        sp.mark("reserved")
        block_ids, table_row = held.block_ids, held.table_row
        if self.tracer is not None:
            self.tracer.span_end(request.request_id, "queue_wait",
                                 shared_blocks=held.shared_blocks,
                                 fresh_blocks=held.span_ids["fresh_blocks"],
                                 resumed=resume is not None)
        if resume is None:
            key = jax.random.PRNGKey(request.seed)
            key, sub = jax.random.split(key)  # generate()'s key schedule
            run = _SlotRun(request, block_ids=block_ids)
            resume_tok = None
        else:
            # the saved key IS the next step's split input — arming with
            # it (and discarding the chunk programs' in-graph samples)
            # keeps seeded-sampled resumes bit-identical
            key = jnp.asarray(np.asarray(resume.key, np.uint32))
            sub = jax.random.PRNGKey(0)            # discarded draw
            run = _SlotRun(request, tokens=list(resume.tokens),
                           t_admit=resume.t_admit, block_ids=block_ids)
            resume_tok = int(resume.tokens[-1])
        sp.mark("keyed")
        run.window, run.chain = held.window, held.chain
        self._slots[slot] = run
        self._prefill_slots.add(slot)
        n_shared = held.shared_blocks * self.kv_block_size
        self.prompt_tokens += L
        self.shared_tokens += n_shared
        self._jobs.append(_PrefillJob(
            run=run, slot=slot, prompt=full, done=n_shared,
            table_row=table_row, key=key, sub=sub,
            temp=jnp.float32(request.temperature),
            topk=jnp.int32(request.top_k),
            topp=jnp.float32(request.top_p), resume_tok=resume_tok))
        return held.span_ids

    def _match_prefix_for_admission(self, full, chain: list) -> List[int]:
        """Admission-time prefix match. The base engine consults only
        its LOCAL index; the fleet's prefill engines override this to
        also fetch a longer chain another worker has registered
        (serving/prefix_cache.py) — either way the returned blocks are
        ref-acquired for the admitting request and ``done`` starts past
        them. ``chain`` receives the digests the local lookup computed."""
        return self.manager.match_prefix(full, chain)

    def admit(self, request) -> bool:
        if not self.try_admit(request):
            raise RuntimeError(
                "KV block pool exhausted; use try_admit/Server (which "
                "re-queue) or raise num_blocks")
        return False

    # -- chunked prefill ---------------------------------------------------
    def prefill_tick(self, token_budget: Optional[int] = None) -> int:
        """Advance pending prefill jobs by up to ``token_budget`` prompt
        tokens (always at least one chunk when work is pending, so a
        tiny budget still progresses). Jobs run FIFO; a finished job
        arms its slot (or retires immediately on eos/max_new==1)."""
        spent = 0
        C = self.prefill_chunk_len
        while self._jobs and (token_budget is None or spent == 0
                              or spent < token_budget):
            # fires BEFORE the chunk dispatch: the job's cursor hasn't
            # advanced, so a retry re-dispatches the identical chunk
            faults.fault_point("serving.prefill_tick")
            job = self._jobs[0]
            L = len(job.prompt)
            n = min(C, L - job.done)
            ids = np.zeros((1, C), np.int32)
            ids[0, :n] = job.prompt[job.done:job.done + n]
            tr = self.tracer
            t_chunk = _trace_now() if tr is not None else 0.0
            with _span("serving.prefill_chunk",
                       rid=job.run.request.request_id,
                       tokens=n) as self._chunk_span:
                tok0_dev, self._cache = self.backend.prefill_chunk(
                    jnp.asarray(ids), self._cache,
                    jnp.asarray(job.table_row[None]),
                    jnp.asarray(job.done, jnp.int32),
                    jnp.asarray(n, jnp.int32),
                    job.sub, job.temp, job.topk, job.topp)
                self._enqueued(self._chunk_span)
            job.done += n
            spent += n
            self.prefill_chunks += 1
            self._chunks_unread += 1
            self.prefilled_tokens += n
            _M_PREFILLS.inc()
            if tr is not None:
                tr.span_at(job.run.request.request_id, "prefill_chunk",
                           t_chunk, tokens=n, done=job.done, total=L)
            if job.done >= L:
                self._jobs.pop(0)
                self._finish_prefill(job, tok0_dev)
        return spent

    def _finish_prefill(self, job: _PrefillJob, tok0_dev):
        req = job.run.request
        now = time.perf_counter()
        eos = req.eos_token_id
        if job.resume_tok is not None:
            # preemption resume: the carried stream owns the next token
            # — the chunk's in-graph sample is discarded, tokens and the
            # TTFT timestamp ride over from the evicted run
            t_synced = time.perf_counter_ns()       # nothing is fetched
            tok0 = job.resume_tok
            rem0 = req.max_new_tokens - len(job.run.tokens)
            req.resume = None
            if self.tracer is not None:
                self.tracer.instant(req.request_id, "resume",
                                    slot=job.slot,
                                    reused_tokens=len(job.run.tokens))
        else:
            began = self._stall_watch.begin()
            with _span("serving.prefill_sync", rid=req.request_id) as sp:
                tok0 = int(tok0_dev)        # host blocked on the last chunk
            t_synced = sp.start + sp.dur
            if self._pending_block is None:     # the last chunk was all
                self._drained_ns = t_synced     # the device had queued
            self._sync_ended(sp, began)
            job.run.tokens = [tok0]
            job.run.t_admit = now           # TTFT timestamp
            self.tokens_emitted += 1
            _M_TOKENS.inc()
            rem0 = req.max_new_tokens - 1
            if eos is not None and tok0 == eos:
                rem0 = 0
        # the prompt's full blocks are resident now — index them so the
        # NEXT request with this prefix skips the compute
        self._register_prompt(job)
        self._prefill_slots.discard(job.slot)
        if rem0 <= 0:                # finished at admission
            self._retire(job.slot, job.run, now)
        else:
            self._arm(job.slot, job.table_row, tok0, len(job.prompt), rem0,
                      eos, job.temp, job.topk, job.topp, job.key)
            if self.tracer is not None:
                self.tracer.span_begin(req.request_id, "decode",
                                       slot=job.slot)
        # host time between the last chunk's end and the next enqueue that
        # no span covers (it is ``serving.tick``'s own): the next
        # ``serving.decode_block`` carries the sum as ``arm_ns``
        self._arm_ns += time.perf_counter_ns() - t_synced

    def _register_prompt(self, job: _PrefillJob):
        """Index the prompt's shareable blocks (now filled) from the
        chain the run carries since its admission, extended over the
        blocks the lookup stopped short of."""
        m, run = self.manager, job.run
        m.register_chain(
            m.extend_chain(run.chain, job.prompt,
                           m._shareable_blocks(job.prompt)),
            run.block_ids)

    def _arm(self, slot, table_row, tok0, pos0, rem0, eos, temp, topk,
             topp, key):
        """Arm ``slot`` for decoding, in-graph and in the host's mirrors
        of its remaining count and position (the one arm site: local
        prefill completion and the fleet's adopted hand-offs)."""
        self._state = self._arm_jit(
            self._state, jnp.int32(slot), jnp.asarray(table_row),
            jnp.int32(tok0), jnp.int32(pos0), jnp.int32(rem0),
            jnp.int32(-1 if eos is None else eos), temp, topk, topp, key)
        self._remaining_host[slot] = rem0
        self._pos_host[slot] = pos0
        self._run_groups[slot] = self._table_run_groups(
            np.asarray(table_row)[None])[0]

    def _table_run_groups(self, rows):
        """:func:`paged_attention.run_groups` of the full-length table's
        columns of ``rows``."""
        from ..ops.pallas.paged_attention import run_groups
        return run_groups(rows[:, :self.max_blocks])

    # -- the decode kernel's walk, counted on the host ----------------------
    def _decode_block_counters(self):
        """Pages one layer's paged decode reads walk over the block about
        to be dispatched, from the host's mirrors of ``pos`` and
        ``remaining`` (no device fetch): at step k a slot's read is
        ``pos + min(k, steps it stays live) + 1`` tokens long, and a dead
        slot re-reads the length it was left at, through its zeroed table
        row. ``ops.pallas.paged_attention.walk_counts`` mirrors the
        kernel's loop bound. A slot that meets its EOS inside the block
        is counted as if it ran to the block's end (at most one page a
        block over). ``kv_pages_in_runs``: of the live pages, those the
        walk moves 8 to a copy (``paged_attention.walk_run_pages``), from
        each decoding slot's row as it was armed; a dead slot reads
        through a zeroed row, page by page."""
        from ..ops.pallas.paged_attention import walk_counts, walk_run_pages
        lengths, decoding = self._block_read_lengths()
        live, copied, _ = walk_counts(lengths, self.max_blocks,
                                      self.kv_block_size)
        in_runs = walk_run_pages(self._run_groups,
                                 np.where(decoding, lengths, 0),
                                 self.kv_block_size)
        self.kv_pages_live += live
        self.kv_pages_copied += copied
        self.kv_pages_in_runs += in_runs
        ids = dict(super()._decode_block_counters(),
                   kv_pages_live=live, kv_pages_copied=copied,
                   kv_pages_in_runs=in_runs, arm_ns=self._arm_ns)
        self._arm_ns = 0
        if self.cache_passes > 1:
            ids["ut_steps"] = self.decode_block * self.cache_passes
            self.ut_steps += ids["ut_steps"]
        return ids

    def _block_read_lengths(self):
        """``(lengths, decoding)``, each ``(decode_block, slots)``: the
        length of every slot's read at every step of the block about to
        be dispatched, and whether the slot is still decoding there."""
        k = np.arange(self.decode_block)[:, None]
        stays = np.minimum(self._remaining_host, self.decode_block)[None, :]
        return self._pos_host[None, :] + np.minimum(k, stays) + 1, k < stays

    def _credit_block(self, toks_np, lives_np, oks_np, rem_np):
        self._pos_host += lives_np.sum(axis=0)      # pos += live, per step
        super()._credit_block(toks_np, lives_np, oks_np, rem_np)

    def _read_program_counters(self):
        return np.asarray(self._cache[-1]) if self._counter_names else None

    def _credit_program_counters(self, counts_np):
        """Fold the programs' counts into the engine's counters and onto
        the spans of what ran: the decode row onto this block's
        ``serving.decode_block``, the chunk row — every chunk since the
        last read — onto the latest ``serving.prefill_chunk`` (with
        ``chunks``, how many it speaks for). Sums are differences of
        wrapping int32 totals; a maximum is the largest so far."""
        spans = (("", self._block_span, {}),
                 ("prefill_", self._chunk_span if self._chunks_unread
                  else None, {"chunks": self._chunks_unread}))
        now = counts_np.astype(np.int64)
        for row, (prefix, span_, ids) in enumerate(spans):
            for col, (name, fold) in enumerate(self._counter_names):
                v = int(now[row, col])
                if fold == "sum":
                    v = (v - int(self._counts_seen[row, col])) % 2 ** 32
                    setattr(self, prefix + name,
                            getattr(self, prefix + name) + v)
                else:
                    setattr(self, prefix + name, v)
                ids[name] = v
            if span_ is not None:
                span_.ids.update(ids)
        self._counts_seen = now
        self._chunks_unread = 0

    def _retire(self, slot, run, now):
        super()._retire(slot, run, now)
        if run.block_ids is not None:
            if run.failure is None and run.tokens:
                # decode-time block sharing: every position the stream
                # WROTE is resident — prompt plus generated history
                # minus the final sampled token (never written). Extend
                # the digest chain over the fully-written blocks so a
                # later request continuing this conversation shares the
                # decode-position KV too. Failed/poisoned runs register
                # NOTHING (a poisoned block must never be matchable).
                # The run's chain already holds the prompt's shareable
                # blocks: only the blocks written since are hashed.
                seq = np.concatenate([
                    np.asarray(run.request.prompt, np.int32).reshape(-1),
                    np.asarray(run.tokens[:-1], np.int32)])
                self._register_written(
                    run, self.manager.extend_chain(
                        run.chain, seq, len(seq) // self.kv_block_size))
            self._release_slot_resources(run)

    def _register_written(self, run, chain):
        """Index the fully-written blocks of a finished stream."""
        self.manager.register_chain(chain, run.block_ids)

    # -- resilience hooks --------------------------------------------------
    def _abort_prefill(self, slot):
        """Cancel a mid-prefill request: drop its pending job (the
        chunk loop never sees it again); its blocks release through the
        shared ``_retire`` path. The slot never armed, so there is no
        in-graph state to kill."""
        self._jobs = [j for j in self._jobs if j.slot != slot]

    def _release_slot_resources(self, run):
        """Retirement and preemption release: the run's arena blocks
        drop one ref — registered prompt-prefix blocks park in the LRU
        cache (their prefix-index entries RETAINED, so a preempted run's
        re-prefill is mostly cache hits), unregistered decode blocks
        return to the free list."""
        if run.block_ids is not None:
            self.manager.release(run.block_ids)
            run.block_ids = None

    def _poison_live_slot(self):
        """Paged poison: NaN the arena block holding the victim's
        position ``pos-1``. That block is always (a) within the slot's
        attended range, so the sentinel trips on the very next step,
        and (b) a FRESH block owned only by this slot — its index
        ``(pos-1)//bs >= (L-1)//bs`` sits past both the shared-prefix
        and the registered range, so no other slot (and no future
        prefix match) can ever read the poison."""
        for slot, run in enumerate(self._slots):
            if run is not None and slot not in self._prefill_slots:
                L = int(np.asarray(run.request.prompt).reshape(-1)
                        .shape[0])
                pos = L + len(run.tokens) - 1
                blk = run.block_ids[(pos - 1) // self.kv_block_size]
                self._cache = tuple(
                    c.at[blk].set(jnp.nan)
                    if jnp.issubdtype(c.dtype, jnp.floating) else c
                    for c in self._cache)
                return slot
        return None

    # -- snapshot / restore ------------------------------------------------
    def snapshot_state(self):
        meta, arrays = super().snapshot_state()
        m = self.manager
        meta["manager"] = {
            "num_blocks": m.num_blocks, "block_size": m.block_size,
            "free": list(m._free),
            "ref": [[int(b), int(r)] for b, r in m._ref.items()],
            "digest_of": [[int(b), d.hex()]
                          for b, d in m._digest_of.items()],
            "index": [[d.hex(), int(bid), [int(t) for t in chunk]]
                      for d, (bid, chunk) in m._index.items()],
            "cached": [int(b) for b in m._cached],   # LRU order
            "lookups": m.lookups, "hit_blocks": m.hit_blocks,
            "depth": [[d.hex(), int(n)] for d, n in m._depth.items()],
            "evictions": m.evictions,
            "hits": [[int(b), int(h)] for b, h in m._hits.items()],
        }
        jobs_meta = []
        for j, job in enumerate(self._jobs):
            arrays[f"job{j}_prompt"] = np.asarray(job.prompt, np.int32)
            arrays[f"job{j}_table"] = np.asarray(job.table_row, np.int32)
            arrays[f"job{j}_key"] = np.asarray(job.key)
            arrays[f"job{j}_sub"] = np.asarray(job.sub)
            jobs_meta.append({
                "slot": job.slot, "done": job.done,
                "temp": float(job.temp), "topk": int(job.topk),
                "topp": float(job.topp), "tok0": job.tok0,
                "resume_tok": job.resume_tok})
        meta["jobs"] = jobs_meta
        meta["paged_counters"] = {
            "prompt_tokens": self.prompt_tokens,
            "shared_tokens": self.shared_tokens,
            "prefilled_tokens": self.prefilled_tokens,
            "prefill_chunks": self.prefill_chunks,
            "fetched_tokens": self.fetched_tokens,
            "kv_pages_live": self.kv_pages_live,
            "kv_pages_copied": self.kv_pages_copied,
            "kv_pages_in_runs": self.kv_pages_in_runs,
            "ut_steps": self.ut_steps}
        return meta, arrays

    def restore_state(self, meta, arrays):
        super().restore_state(meta, arrays)
        mm = meta["manager"]
        m = self.manager
        if (mm["num_blocks"], mm["block_size"]) != (m.num_blocks,
                                                   m.block_size):
            raise ValueError(
                f"snapshot arena {mm['num_blocks']}x{mm['block_size']} "
                f"does not match this engine's "
                f"{m.num_blocks}x{m.block_size}")
        m._free = list(mm["free"])
        m._ref = {int(b): int(r) for b, r in mm["ref"]}
        m._digest_of = {int(b): bytes.fromhex(d)
                        for b, d in mm["digest_of"]}
        m._index = {bytes.fromhex(d): (int(bid), tuple(chunk))
                    for d, bid, chunk in mm["index"]}
        m._cached = OrderedDict((int(b), None) for b in mm["cached"])
        m.lookups, m.hit_blocks = mm["lookups"], mm["hit_blocks"]
        m._depth = {bytes.fromhex(d): int(n)
                    for d, n in mm.get("depth", [])}
        m._depth = {d: n for d, n in m._depth.items()
                    if d in m._index}
        m.evictions = int(mm.get("evictions", 0))
        m._hits = {int(b): int(h) for b, h in mm.get("hits", [])
                   if int(b) in m._digest_of}
        m._reindex_cached()
        m.assert_consistent()
        self._jobs = []
        for j, jm in enumerate(meta["jobs"]):
            run = self._slots[jm["slot"]]
            self._jobs.append(_PrefillJob(
                run=run, slot=jm["slot"],
                prompt=np.asarray(arrays[f"job{j}_prompt"], np.int32),
                done=jm["done"],
                table_row=np.asarray(arrays[f"job{j}_table"], np.int32),
                key=jnp.asarray(arrays[f"job{j}_key"]),
                sub=jnp.asarray(arrays[f"job{j}_sub"]),
                temp=jnp.float32(jm["temp"]),
                topk=jnp.int32(jm["topk"]),
                topp=jnp.float32(jm["topp"]), tok0=jm["tok0"],
                resume_tok=jm.get("resume_tok")))
        pc = meta["paged_counters"]
        self.prompt_tokens = pc["prompt_tokens"]
        self.shared_tokens = pc["shared_tokens"]
        self.prefilled_tokens = pc["prefilled_tokens"]
        self.prefill_chunks = pc["prefill_chunks"]
        self.fetched_tokens = pc.get("fetched_tokens", 0)
        self.kv_pages_live = pc.get("kv_pages_live", 0)
        self.kv_pages_copied = pc.get("kv_pages_copied", 0)
        self.kv_pages_in_runs = pc.get("kv_pages_in_runs", 0)
        self.ut_steps = pc.get("ut_steps", 0)
        self._pos_host = np.asarray(self._state["pos"]).astype(np.int64)
        self._run_groups = self._table_run_groups(
            np.asarray(self._state["table"]))
