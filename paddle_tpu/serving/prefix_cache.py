"""Fleet-wide KV prefix cache: warm prefix state as a storage tier.

PR 4 gave every paged engine a worker-LOCAL prefix index: full prompt
blocks keyed by a chained-SHA1 digest, LRU-retained at refcount 0, so a
hot system prompt survives across requests on that worker. At fleet
scale the same prefix is re-prefilled once per worker it lands on — the
dominant avoidable prefill cost. This module turns the local index
into a fleet tier:

- :class:`PrefixCacheDirectory` — the fleet-level catalog. Each
  heartbeat, every paged worker publishes its registered digest chains
  (``BlockManager.registered_chains()``: digest → covered block count),
  so directory state rides the PR 15 lease machinery: a dead worker's
  entries drop with its lease, an evicted block's digest vanishes on
  the owner's next beat. Lookup walks the REQUESTER's digest chain and
  returns the deepest prefix some single live owner covers
  consecutively from the root (an owner holding only a chain tail
  cannot serve it — its ``match_prefix`` walks from the root too).
- :func:`extract_prefix` / :func:`adopt_prefix` — the remote fetch.
  The owner re-matches the token prefix against its OWN index (ref-
  acquiring the blocks for the copy, token-compared so a hash collision
  degrades to a shorter match, never a wrong block), ships the covered
  block rows at storage dtype as a ``pt-kv-fetch`` payload over the
  same v1 serializer/CRC machinery as KV handoffs, and the requester
  adopts them through the PR 15 idempotent-adopt scatter
  (:func:`_adopt_scatter` — the SAME program shape
  ``DecodeWorker.adopt`` uses, zero new compiled programs on the
  decode/prefill steady paths), registers the chain in its own index,
  and chunk-prefills only the uncovered suffix.
- Cross-TP-layout fetches: a sharded owner ships per-shard chunks
  along the kv-head axis; the requester re-chunks them to its own
  degree via ``handoff.reshard_kv_chunks`` (arXiv:2112.01075 — peak
  footprint one part) before the logical scatter, and its backend's
  ``commit_arrays`` hook re-commits onto the local mesh.

Failure semantics: a fetch that fails for ANY reason — owner dead
mid-fetch, injected ``fleet.fetch`` fault past the retry budget, CRC
mismatch, stale directory (owner evicted the blocks since its last
beat), requester pool full — falls back to LOCAL PREFILL. The request
never fails because a warm copy was advertised; remote state is an
optimization tier, not a dependency.

Metric families (registered at import; no-ops until
``metrics.enable()``/``PT_METRICS``): fetches, fetched blocks/bytes,
failures by reason, duplicate responses dropped, directory entries.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..observability import metrics as _om
from .handoff import FETCH_FORMAT, KVHandoff, reshard_kv_chunks
from .paging import walk_chain

__all__ = ["PrefixCacheDirectory", "adopt_prefix", "extract_prefix"]

#: kv-head axis of every pool leaf (4D arenas and 3D int8 scale
#: arrays alike) — the axis serving/tp.py shards and cross-layout
#: fetches re-chunk.
KV_HEAD_AXIS = 2

_M_FETCHES = _om.counter("pt_prefix_fetches_total",
                         "remote prefix fetches adopted")
_M_FETCH_BLOCKS = _om.counter("pt_prefix_fetch_blocks_total",
                              "KV blocks adopted from remote prefix "
                              "fetches")
_M_FETCH_BYTES = _om.counter("pt_prefix_fetch_bytes_total",
                             "wire bytes of adopted prefix-fetch "
                             "payloads")
_M_FETCH_FAILS = _om.counter("pt_prefix_fetch_failures_total",
                             "prefix fetches that fell back to local "
                             "prefill, by reason", labels=("reason",))
_M_FETCH_DUPS = _om.counter("pt_prefix_fetch_duplicates_total",
                            "stale/duplicate fetch responses dropped "
                            "(at-least-once wire retransmits)")
_M_DIR_ENTRIES = _om.gauge("pt_prefix_directory_entries",
                           "distinct digest chains in the fleet prefix "
                           "directory")


class PrefixCacheDirectory:
    """Fleet-level map of registered prefix chains to owning workers.

    State is heartbeat-shaped: :meth:`publish` REPLACES a worker's
    entry set wholesale (the worker's ``registered_chains()`` snapshot
    is the truth; anything it evicted since the last beat simply stops
    being listed), and :meth:`drop_worker` removes a dead worker's
    entries the moment its lease expires. The directory stores no
    token data — hash collisions are caught owner-side at extract
    time by the index's stored-token comparison."""

    def __init__(self):
        self._by_worker: Dict[str, Dict[bytes, int]] = {}
        self._owners: Dict[bytes, set] = {}

    def publish(self, worker: str, chains: Dict[bytes, int]):
        """Replace ``worker``'s published digest set."""
        old = self._by_worker.get(worker, {})
        for digest in old:
            if digest not in chains:
                self._unlist(digest, worker)
        for digest in chains:
            if digest not in old:
                self._owners.setdefault(digest, set()).add(worker)
        self._by_worker[worker] = dict(chains)
        self._note()

    def drop_worker(self, worker: str):
        """Expire every entry the worker published (lease death)."""
        for digest in self._by_worker.pop(worker, {}):
            self._unlist(digest, worker)
        self._note()

    def _unlist(self, digest: bytes, worker: str):
        owners = self._owners.get(digest)
        if owners is not None:
            owners.discard(worker)
            if not owners:
                del self._owners[digest]

    def _note(self):
        if _om.enabled():
            _M_DIR_ENTRIES.set(len(self._owners))

    def owners(self, digest: bytes) -> Tuple[str, ...]:
        return tuple(sorted(self._owners.get(digest, ())))

    def size(self) -> int:
        return len(self._owners)

    def worker_entries(self, worker: str) -> Dict[bytes, int]:
        return dict(self._by_worker.get(worker, {}))

    def deepest_covered(self, prompt, block_size: int, hash_fn,
                        exclude: Iterable[str] = ()
                        ) -> Tuple[int, Tuple[str, ...]]:
        """Walk ``prompt``'s digest chain and return ``(n_blocks,
        owners)``: the deepest full-block prefix that at least one
        worker (outside ``exclude``) covers CONSECUTIVELY from the
        root, and the workers that do. A worker listing only a chain
        tail (its chain head was LRU-evicted) is not an owner — its
        own ``match_prefix`` could not serve the fetch."""
        excl = set(exclude)
        best: Tuple[int, Tuple[str, ...]] = (0, ())
        alive: Optional[set] = None
        for depth, (digest, _) in enumerate(walk_chain(
                prompt, block_size, (len(prompt) - 1) // block_size,
                hash_fn), 1):
            cand = {o for o in self._owners.get(digest, ())
                    if o not in excl}
            alive = cand if alive is None else (alive & cand)
            if not alive:
                break
            best = (depth, tuple(sorted(alive)))
        return best

    def stats(self) -> dict:
        return {"entries": len(self._owners),
                "workers": sorted(self._by_worker),
                "deepest_chain": max(
                    (n for c in self._by_worker.values()
                     for n in c.values()), default=0)}


def _adopt_scatter(cache_flat, rows_flat, table):
    """ONE fixed-shape scatter arming adopted KV rows into an arena —
    shared by ``DecodeWorker.adopt`` (handoffs) and
    :func:`adopt_prefix` (fetches). Rows are padded to ``max_blocks``;
    pad rows write zeros into the reserved trash block (the table tail
    is 0), so the program shape never depends on the payload."""
    return tuple(c.at[table].set(r.astype(c.dtype))
                 for c, r in zip(cache_flat, rows_flat))


def extract_prefix(engine, tokens, n_blocks: int, skip: int = 0,
                   source: str = "") -> Optional[KVHandoff]:
    """Owner-side fetch service: build a ``pt-kv-fetch`` payload with
    the arena rows of blocks ``[skip, n_blocks)`` of ``tokens``'s
    digest chain. Returns None when this engine's index no longer
    covers ``n_blocks`` consecutive blocks (the directory was stale —
    the requester falls back to local prefill). The matched blocks are
    ref-acquired for the duration of the copy and released before
    returning, so concurrent eviction can never tear the payload."""
    bs = engine.kv_block_size
    sub = np.asarray(tokens[:n_blocks * bs + 1], np.int32)
    blocks = engine.manager.match_prefix(sub)
    if len(blocks) < n_blocks:
        engine.manager.release(blocks)
        return None
    ids = np.asarray(blocks[skip:n_blocks], np.int32)
    src_tp = engine.tp_degree()
    arrays: Dict[str, np.ndarray] = {"tokens": sub}
    for i, c in enumerate(engine._cache):
        rows = np.asarray(c[ids])
        if src_tp > 1:
            # a sharded owner ships per-shard chunks along the kv-head
            # axis; the requester reshards them to ITS degree
            for s, piece in enumerate(
                    np.split(rows, src_tp, axis=KV_HEAD_AXIS)):
                arrays[f"kv_{i}_p{s}"] = np.ascontiguousarray(piece)
        else:
            arrays[f"kv_{i}"] = rows
    engine.manager.release(blocks)
    meta = {
        "format": FETCH_FORMAT, "kind": "prefix",
        "n_blocks": int(n_blocks), "skip": int(skip),
        "block_size": int(bs), "kv_int8": bool(engine.kv_int8),
        "leaf_specs": [[list(s[1:]), str(np.dtype(d))]
                       for s, d in engine.backend.pool_specs],
        "src_tp_degree": int(src_tp),
        "source": {"worker": source},
    }
    return KVHandoff(meta=meta, arrays=arrays)


def _logical_rows(h: KVHandoff, leaf: int, src_tp: int,
                  dst_tp: int) -> np.ndarray:
    """Reassemble one leaf's logical block rows from the payload —
    directly for an unsharded source, via ``reshard_kv_chunks`` for a
    sharded one (int8 scale leaves ride the same path: they are just
    another leaf with the kv-head axis in the same place)."""
    direct = h.arrays.get(f"kv_{leaf}")
    if direct is not None:
        return direct
    parts = [h.arrays[f"kv_{leaf}_p{s}"] for s in range(src_tp)]
    total = sum(p.shape[KV_HEAD_AXIS] for p in parts)
    if dst_tp > 1 and total % dst_tp == 0:
        parts = reshard_kv_chunks(parts, dst_tp, axis=KV_HEAD_AXIS)
    return np.concatenate(parts, axis=KV_HEAD_AXIS) \
        if len(parts) > 1 else parts[0]


def adopt_prefix(engine, h: KVHandoff, local_blocks: List[int],
                 full) -> Optional[List[int]]:
    """Requester-side adopt: scatter the fetched block rows into this
    engine's arena at exact refcounts and register the extended chain.

    Allocates ``n_blocks - skip`` fresh blocks (refcount 1 — the same
    hold the admitting request would have acquired by matching them
    locally), scatters through the shared :func:`_adopt_scatter`
    program, registers ``local_blocks + fetched`` under the prompt's
    digest chain (so the copy is immediately matchable AND publishable
    here), and re-commits via the backend's ``commit_arrays`` hook on
    TP targets. Returns the fetched block ids, or None when the pool
    cannot cover them (caller falls back to local prefill). Raises
    ValueError on an incompatible payload — geometry mismatches are
    bugs, not fallbacks."""
    import jax
    meta = h.meta
    if meta.get("kind") != "prefix":
        raise ValueError(
            f"{meta.get('kind')!r} payload on the prefix-fetch channel")
    specs = [[list(s[1:]), str(np.dtype(d))]
             for s, d in engine.backend.pool_specs]
    if meta["leaf_specs"] != specs:
        raise ValueError(
            "prefix-fetch KV layout does not match this engine — same "
            "model config / paging layout required")
    if meta["block_size"] != engine.kv_block_size \
            or bool(meta["kv_int8"]) != bool(engine.kv_int8):
        raise ValueError(
            "prefix-fetch arena geometry mismatch (block_size/kv_int8)")
    n_blocks, skip = int(meta["n_blocks"]), int(meta["skip"])
    k = n_blocks - skip
    if k <= 0 or len(local_blocks) != skip:
        raise ValueError(
            f"prefix-fetch covers blocks [{skip}, {n_blocks}) but the "
            f"requester holds {len(local_blocks)} local blocks")
    fetched = engine.manager.allocate(k)
    if fetched is None:
        return None
    src_tp = int(meta.get("src_tp_degree", 1))
    dst_tp = engine.tp_degree()
    table = np.zeros((engine.max_blocks,), np.int32)
    table[:k] = fetched
    rows = []
    for i, (shape, dtype) in enumerate(engine.backend.pool_specs):
        r = np.zeros((engine.max_blocks,) + tuple(shape[1:]),
                     np.dtype(dtype))
        r[:k] = _logical_rows(h, i, src_tp, dst_tp)
        rows.append(r)
    jit = getattr(engine, "_prefix_adopt_jit", None)
    if jit is None:
        jit = jax.jit(_adopt_scatter, donate_argnums=(0,))
        engine._prefix_adopt_jit = jit
    engine._cache = jit(engine._cache, tuple(rows), table)
    bs = engine.kv_block_size
    engine.manager.register_prefix(
        np.asarray(full[:n_blocks * bs + 1], np.int32),
        list(local_blocks) + fetched)
    commit = getattr(engine.backend, "commit_arrays", None)
    if commit is not None:
        engine._cache, engine._state = commit(engine._cache,
                                              engine._state)
    return fetched
