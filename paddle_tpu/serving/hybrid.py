"""The paged engine for a model whose cache keeps TWO GROUPS of layers:
full-attention layers that hold every token, and sliding-window layers
that ever read back only their last ``window`` tokens (``models/mimo_v2.py``,
packed grouped-query arenas; ``models/dots3_note.py``, latent arenas, a full
layer's leaf a PAIR of arenas, latent rows and index keys, under one block
id; the model says so through ``kv_cache_groups``).

Each group has its own arena geometry (the model's), its own pool of
blocks with its own :class:`~.paging.BlockManager`, and its own block
table: a slot's table row is the full group's ``max_blocks`` columns
followed by the window group's. The FULL group behaves as
:class:`~.paging.PagedEngine`'s one pool does. The WINDOW group holds
O(window) tokens a slot whatever ``max_len`` is. Its row is written once,
at admission, as the full group's is:

- columns before the hit's last window -> the trash block 0 (the decode
  walk starts at the first page inside the window and the gathered read
  takes only the columns a window spans, so they are never read);
- the ``tail_blocks`` columns before the cut of a prefix hit -> the
  retained blocks that hold that prefix's last ``window - 1`` tokens
  (shared, read-only, ref-counted);
- every later column cycles over the slot's OWN ring of ``ring_blocks =
  cdiv(window - 1 + longest write, block) + 1`` blocks: a write at column
  j lands on what the slot wrote at column ``j - ring_blocks``, whose
  tokens no read from j on can see. (The other design, blocks given back
  as a slot moves on, needs allocation during decode or a whole prompt's
  blocks at admission; the ring needs neither and the row never changes.)

A prefix is a HIT only as far as the full group holds every block AND the
window group holds the tail before the cut; a request that hits prefills
only the rest, in both groups. A ring is overwritten as its slot moves on,
so the window group can retain a tail only where blocks were set aside
for one:

- where a request's match in the full group ends at a cut whose tail the
  window group does not hold (a prefix seen for the second time, diverging
  there), the request gives those ``tail_blocks`` columns blocks of their
  own, outside its ring, and registers them when its prefill has written
  them: the NEXT request with that prefix hits;
- when a stream retires, the ring still holds its last window intact:
  the tail before its last full block is registered as it is, so a
  request that continues the conversation hits.

What the hybrid cache cannot do yet refuses by name: an int8 arena,
tensor parallelism, speculation, an explicit or exported backend, the
fleet's hand-off, snapshot / restore, preemption (``can_resume`` is False,
so a priority-aware server never evicts from it).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .paging import (BlockManager, PagedEngine, PagedModelStepBackend,
                     _Reservation)

__all__ = ["HybridPagedEngine", "HybridPagedStepBackend"]

FULL, WINDOW = 0, 1             # a cache leaf's group (the model's values)


class HybridPagedStepBackend(PagedModelStepBackend):
    """``PagedModelStepBackend`` over a cache with a second, window,
    group: that group's arenas have ``window_blocks`` blocks and a slot's
    table row carries both groups' columns."""

    def __init__(self, model, num_slots, max_len, decode_block, block_size,
                 num_blocks, window_blocks, prefill_chunk, quant=None):
        groups = model.kv_cache_groups
        self.window = int(groups["window"])
        self.leaf_group = tuple(groups["leaf_group"])
        self.num_window_blocks = window_blocks
        super().__init__(model, num_slots, max_len, decode_block,
                         block_size, num_blocks, False, prefill_chunk,
                         quant=quant)

    def _new_cache(self, model):
        """The model's cache; ``leaf_group`` then names the group of every
        ARENA: a layer's leaf may be a tuple of arenas of one group that
        share a block id (latent rows and their index keys)."""
        import jax
        cache = model.init_paged_kv_cache(
            self.num_kv_blocks, self.kv_block_size,
            window_blocks=self.num_window_blocks)
        groups, arenas = self.leaf_group, []
        for leaf, group in zip(cache["layers"], groups):
            arenas += [group] * len(jax.tree.leaves(
                leaf, is_leaf=lambda x: not isinstance(x, (tuple, list))))
        self.leaf_group = tuple(arenas) + groups[len(cache["layers"]):]
        return cache

    table_width = property(lambda self: 2 * self.max_blocks)


@dataclass
class _WindowHold:
    """What a run holds in the window pool: every block to release, the
    window group's table row (column -> block), and the positions
    ``(first, stop)`` of the run's chain to register once the prefill has
    written them."""
    block_ids: List[int]
    row: np.ndarray
    plant: Optional[Tuple[int, int]] = None


class HybridPagedEngine(PagedEngine):
    """Paged continuous batching over two groups of cache layers (module
    docstring). ``num_blocks`` sizes the full group's pool,
    ``window_blocks`` the window group's (default: every slot's ring and
    two tails, plus the trash block)."""

    def __init__(self, model=None, num_slots: int = 4, max_len: int = 256,
                 decode_block: int = 8, prompt_buckets=None, backend=None,
                 *, paged: bool = True, spec=None, window_blocks=None,
                 tp=None, **kw):
        from .tp import resolve_tp_config
        if backend is not None:
            raise NotImplementedError(
                "the hybrid cache builds its own step backend: an explicit "
                "(or exported) backend cannot hold its two groups yet")
        if spec:
            raise NotImplementedError(
                "speculative decoding over the hybrid cache: the verify "
                "window's writes are not carried over to the ring")
        if resolve_tp_config(tp) is not None:
            raise NotImplementedError(
                "tensor-parallel serving of the hybrid cache: the packed "
                "arenas have no sharding rule yet")
        self._window_blocks_arg = window_blocks
        self.window_manager = None
        super().__init__(model, num_slots, max_len, decode_block,
                         prompt_buckets, None, paged=paged, **kw)

    def _build_paged_backend(self, model, num_slots, max_len, decode_block,
                             block_size, num_blocks, kv_int8, prefill_chunk,
                             quant=None):
        if kv_int8:
            raise NotImplementedError(
                "kv_int8 over the hybrid cache: the packed arena has no "
                "int8 form")
        window = int(model.kv_cache_groups["window"])
        self.tail_blocks = -(-(window - 1) // block_size)
        self.ring_blocks = -(-(window - 1 + prefill_chunk) // block_size) + 1
        wb = self._window_blocks_arg
        if wb is None:
            wb = 1 + num_slots * (self.ring_blocks + 2 * self.tail_blocks)
        return HybridPagedStepBackend(
            model, num_slots, max_len, decode_block, block_size, num_blocks,
            wb, prefill_chunk, quant=quant)

    # -- lifecycle ---------------------------------------------------------
    def reset(self):
        if self.window_manager is None:
            self.window_manager = BlockManager(
                self.backend.num_window_blocks, self.kv_block_size,
                self.manager.hash_fn)
        super().reset()
        self.window_manager.reset()
        self.window = self.backend.window
        self.num_window_blocks = self.backend.num_window_blocks
        # the window layers' decode walk (per window layer), beside the
        # full layers' ``kv_pages_live`` / ``kv_pages_copied``
        self.window_kv_pages_live = 0
        self.window_kv_pages_copied = 0
        # rows a layer's decode reads MUST see, summed over live slots and
        # decode steps: every live token (a full layer), those inside the
        # window (a window layer)
        self.kv_rows_live = 0
        self.window_kv_rows_live = 0

    def validate_request(self, prompt_len: int, max_new_tokens: int):
        super().validate_request(prompt_len, max_new_tokens)
        need = self.ring_blocks + self.tail_blocks
        pool = self.window_manager.usable_blocks()
        if need > pool:
            raise ValueError(
                f"a request needs up to {need} window-group blocks (its "
                f"ring and one tail) but that pool only has {pool}; raise "
                "window_blocks")

    # -- admission ---------------------------------------------------------
    def _reserve(self, full, mnt) -> Optional[_Reservation]:
        """Both groups' blocks and the two-table row (module docstring).
        The hit's cut is the longest prefix whose every block the full
        group holds AND whose tail the window group holds."""
        fm, wm, T = self.manager, self.window_manager, self.tail_blocks
        chain = []
        found = fm.find_prefix(full, chain)
        wm.lookups += 1

        def tail(n):
            """The window pool's blocks of the tail before a cut at n
            blocks, or None where one is missing."""
            out = []
            for j in range(n - 1, max(n - T, 0) - 1, -1):
                b = wm.block_of(found[j][0], found[j][1])
                if b is None:
                    return None
                out.append(b)
            return out[::-1]

        cut, shared_w = 0, []
        for n in range(len(found), 0, -1):
            got = tail(n)
            if got is not None:
                cut, shared_w = n, got
                break
        shared = [b for _, _, b in found[:cut]]
        # held before anything is allocated, so that an allocation's
        # eviction cannot take them
        fm.acquire_hits(shared)
        wm.acquire_hits(shared_w)
        total = self.blocks_needed(len(full), mnt)
        # the full group matched further than the window group holds a
        # tail: set that tail's columns aside, to be registered
        planted = list(range(max(cut, len(found) - T), len(found)))
        ring_cols = [j for j in range(cut, total) if j not in set(planted)]
        n_ring = min(self.ring_blocks, len(ring_cols))
        evicted_w = wm.evictions
        fresh = fm.allocate(total - cut)
        own_w = None if fresh is None else \
            wm.allocate(n_ring + len(planted))
        if own_w is None:               # a pool is exhausted: retry later
            fm.release(shared + (fresh or []))
            wm.release(shared_w)
            return None
        mb = self.max_blocks
        row = np.zeros((2 * mb,), np.int32)
        row[:total] = shared + fresh
        wrow = row[mb:]
        wrow[max(cut - T, 0):cut] = shared_w
        wrow[planted] = own_w[n_ring:]
        wrow[ring_cols] = np.asarray(own_w[:n_ring], np.int32)[
            np.arange(len(ring_cols)) % max(n_ring, 1)]
        hold = _WindowHold(shared_w + own_w, wrow,
                           (planted[0], len(found)) if planted else None)
        return _Reservation(
            shared + fresh, row, cut,
            {"fresh_blocks": len(fresh), "window_blocks": len(own_w),
             "shared_window_blocks": len(shared_w),
             "window_evicted_blocks": wm.evictions - evicted_w},
            chain, window=hold)

    def _try_admit(self, request, sp):
        if getattr(request, "resume", None) is not None:
            raise NotImplementedError(
                "preemption resume over the hybrid cache is not carried "
                "over (can_resume is False)")
        return super()._try_admit(request, sp)

    def _register_prompt(self, job):
        super()._register_prompt(job)
        hold = job.run.window
        if hold.plant is not None:
            first, stop = hold.plant
            self.window_manager.register_chain(job.run.chain[:stop],
                                               hold.row, first)

    def _register_written(self, run, chain):
        """The full group indexes every written block; the window group
        the tail before the last one, which the ring still holds."""
        super()._register_written(run, chain)
        self.window_manager.register_chain(
            chain, run.window.row, max(len(chain) - self.tail_blocks, 0))

    def _release_slot_resources(self, run):
        super()._release_slot_resources(run)
        if run.window is not None:
            self.window_manager.release(run.window.block_ids)
            run.window = None

    # -- introspection -----------------------------------------------------
    def window_kv_resident(self) -> Tuple[int, int]:
        """``(window-group blocks the live slots hold, shared tails
        counted once; the live slots' tokens)``: times the block size and
        divided, what the window layers keep of what storing every token
        would."""
        blocks, tokens = set(), 0
        for _, run in self.live_runs():
            blocks.update(run.window.block_ids)
            tokens += len(run.request.prompt) + len(run.tokens)
        return len(blocks), tokens

    def _decode_block_counters(self):
        """The full layers' walk from the base engine; the window
        layers' beside it (``walk_counts`` under the window)."""
        from ..ops.pallas.paged_attention import walk_counts
        lengths, decoding = self._block_read_lengths()
        live, copied, _ = walk_counts(lengths, self.max_blocks,
                                      self.kv_block_size, window=self.window)
        self.window_kv_pages_live += live
        self.window_kv_pages_copied += copied
        rows = int((lengths * decoding).sum())
        window_rows = int((np.minimum(lengths, self.window) * decoding).sum())
        self.kv_rows_live += rows
        self.window_kv_rows_live += window_rows
        return dict(super()._decode_block_counters(),
                    window_kv_pages_live=live, window_kv_pages_copied=copied,
                    kv_rows_live=rows, window_kv_rows_live=window_rows)

    # -- what is not carried over ------------------------------------------
    def can_resume(self, run) -> bool:
        return False

    def preempt_slot(self, slot: int):
        raise NotImplementedError(
            "preemption over the hybrid cache is not carried over: the "
            "window group's blocks have no release-and-resume path yet")

    def snapshot_state(self):
        raise NotImplementedError(
            "snapshot of the hybrid cache is not carried over: the window "
            "pool's manager and the runs' rings are not serialized yet")

    def restore_state(self, meta, arrays):
        raise NotImplementedError(
            "restore into the hybrid cache is not carried over")

    def _poison_live_slot(self):
        """As the paged engine's, in the full group's arenas only: the
        block id is the full pool's, and means another slot's block (or
        none) in a window arena."""
        for slot, run in enumerate(self._slots):
            if run is not None and slot not in self._prefill_slots:
                pos = len(np.asarray(run.request.prompt).reshape(-1)) \
                    + len(run.tokens) - 1
                blk = run.block_ids[(pos - 1) // self.kv_block_size]
                self._cache = tuple(
                    c.at[blk].set(jnp.nan) if group == FULL else c
                    for c, group in zip(self._cache,
                                        self.backend.leaf_group))
                return slot
        return None
