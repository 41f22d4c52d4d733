"""KV-block handoff wire format for the disaggregated serving fleet.

A prefill worker that finishes a prompt owns exactly the state a decode
worker needs to continue the stream: the slot's KV blocks, the in-hand
first token, the post-split rng key, the remaining token budget and the
request itself. This module defines that payload as a **versioned,
bytes-true wire format**:

- ``KVHandoff`` is the in-memory form: a JSON-safe ``meta`` dict plus a
  dict of numpy arrays (prompt, rng key, per-layer KV block data).
- ``encode_handoff()``/``decode_handoff()`` round-trip it through ONE
  uncompressed npz byte buffer (no pickle — same discipline as the
  PR 5 snapshot format); ``len(encode_handoff(h))`` is the real wire
  size.
- **Bytes-true**: arrays ship at their storage dtype. An int8 KV arena
  ships int8 codes + fp32 absmax scales and is NEVER dequantized in
  transit — the wire payload is ~3.6x smaller than the fp32 arena's
  (4d/(d+4) at head_dim d), which is the point of quantizing it.
- Only the blocks holding PROMPT positions ship (``ceil(L/bs)`` of the
  request's ``blocks_needed`` total): decode-position blocks are junk
  the decode worker writes before it ever reads, so they cost zero
  wire bytes.

The format is **layout-free**: arrays are logical (host-gathered), so a
payload extracted from a TP-sharded source adopts onto any target mesh
— the target engine re-commits through its backend's ``commit_arrays``
hook, the same path snapshot restore uses. For transports that ship
per-shard chunks instead (a real network fleet), ``reshard_kv_chunks``
re-chunks a sharded KV axis between source and target TP degrees one
output part at a time (the memory-efficient redistribution discipline
of arXiv:2112.01075: peak footprint is one part, never the whole
transfer).

Fault sites (``utils.faults``): ``fleet.serialize`` fires in
``encode_handoff()`` before any bytes are produced, so a retry re-extracts and
re-serializes the identical payload.
"""
from __future__ import annotations

import io
import json
from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

from ..utils import faults

__all__ = ["FETCH_FORMAT", "HANDOFF_FORMAT", "HANDOFF_VERSION",
           "KVHandoff", "decode_handoff", "encode_handoff",
           "reshard_kv_chunks"]

HANDOFF_FORMAT = "pt-kv-handoff"
# prefix-fetch responses (serving/prefix_cache.py) ride the SAME v1
# serializer/CRC/validation machinery under their own format stamp, so
# a fetch payload mis-delivered to an adopt path is refused by kind,
# never silently armed as a stream
FETCH_FORMAT = "pt-kv-fetch"
HANDOFF_VERSION = 1
# the one payload kind each format carries
_KIND_OF_FORMAT = {HANDOFF_FORMAT: "paged", FETCH_FORMAT: "prefix"}


@dataclass
class KVHandoff:
    """One slot's portable handoff payload.

    ``meta`` (JSON-safe): ``format``/``version``, ``kind`` ("paged"),
    the serialized request (``resilience.request_to_meta``), the
    armed-slot scalars (``tok0``, ``pos0``, ``rem0``), the arena geometry
    (``n_blocks`` total to allocate, ``n_ship`` actually shipped,
    ``block_size``, ``kv_int8``), per-leaf block specs for
    compatibility validation, the first-token timestamp ``t_admit``
    (TTFT keeps measuring the prefill worker's first token), and the
    ``source`` worker name + TP degree.

    ``arrays``: ``prompt`` (int32), ``key`` ((2,) uint32 — the
    post-split state key, i.e. the NEXT decode step's split input),
    and ``kv_<i>`` per cache leaf: ``(n_ship, bs, ...)`` block rows at
    storage dtype.
    """
    meta: dict
    arrays: Dict[str, np.ndarray]

    @property
    def request_id(self) -> int:
        return self.meta["request"]["request_id"]

    @property
    def kind(self) -> str:
        return self.meta["kind"]

    def kv_bytes(self) -> int:
        """Bytes of KV payload on the wire (codes + scales at storage
        dtype) — the number the fp32-vs-int8 bench ratio compares."""
        return sum(int(v.nbytes) for k, v in self.arrays.items()
                   if k.startswith("kv_"))

    def payload_crc32(self) -> int:
        """CRC32 over every array's name, dtype, shape and raw bytes
        (name-sorted, so the digest is layout-order independent). The
        fleet stamps it into ``meta["crc32"]`` at ship time;
        ``DecodeWorker.adopt`` recomputes it BEFORE touching any
        allocator state — a tampered/corrupted payload is refused
        loudly, never scattered into an arena."""
        import zlib
        c = 0
        for name in sorted(self.arrays):
            a = np.ascontiguousarray(self.arrays[name])
            c = zlib.crc32(
                f"{name}|{a.dtype}|{a.shape}".encode(), c)
            c = zlib.crc32(a.tobytes(), c)
        return c & 0xFFFFFFFF

    def verify_crc(self):
        """Raise ValueError when ``meta["crc32"]`` (if present) does
        not match the arrays actually carried."""
        want = self.meta.get("crc32")
        if want is not None and int(want) != self.payload_crc32():
            raise ValueError(
                f"handoff payload CRC mismatch (rid {self.request_id})"
                " — refusing to adopt corrupted KV state")


def encode_handoff(handoff: KVHandoff) -> bytes:
    """Serialize to one uncompressed npz byte string (bytes-true:
    int8 stays int8 on the wire). The ``fleet.serialize`` fault site
    fires BEFORE any bytes exist, so a retry is side-effect free."""
    faults.fault_point("fleet.serialize")
    bio = io.BytesIO()
    payload = dict(handoff.arrays)
    payload["__meta__"] = np.array(json.dumps(
        {"format": HANDOFF_FORMAT, "version": HANDOFF_VERSION,
         **handoff.meta}))
    np.savez(bio, **payload)
    return bio.getvalue()


def decode_handoff(data: bytes) -> KVHandoff:
    """Inverse of :func:`encode_handoff`; refuses foreign or future-versioned
    payloads, and a payload whose kind is not its format's, loudly
    instead of adopting garbage into an arena."""
    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    if meta.get("format") not in _KIND_OF_FORMAT:
        raise ValueError("payload is not a KV handoff")
    if meta.get("kind") != _KIND_OF_FORMAT[meta["format"]]:
        raise ValueError(
            f"{meta.get('kind')!r} payload in a {meta['format']} — this "
            f"build reads {_KIND_OF_FORMAT[meta['format']]!r} ones only")
    if meta.get("version") != HANDOFF_VERSION:
        raise ValueError(
            f"KV handoff version {meta.get('version')} unsupported "
            f"(this build reads {HANDOFF_VERSION})")
    return KVHandoff(meta=meta, arrays=arrays)


def reshard_kv_chunks(chunks: Sequence[np.ndarray], dst_parts: int,
                      axis: int = 1) -> List[np.ndarray]:
    """Re-chunk per-shard KV pieces from a source TP degree to a target
    degree along ``axis`` (the kv-head axis for this repo's sharding).

    Portable redistribution per arXiv:2112.01075: each output part is
    assembled from exactly the input slices that cover its index range,
    so peak memory is ONE output part — the full logical array is never
    materialized. ``concatenate(result) == concatenate(chunks)`` along
    ``axis`` by construction (identity-pinned in tests)."""
    if dst_parts < 1:
        raise ValueError(f"dst_parts={dst_parts}; must be >= 1")
    sizes = [c.shape[axis] for c in chunks]
    total = sum(sizes)
    if total % dst_parts != 0:
        raise ValueError(
            f"axis extent {total} does not divide into {dst_parts} "
            "target shards")
    per = total // dst_parts
    starts = np.cumsum([0] + sizes)
    out: List[np.ndarray] = []
    for j in range(dst_parts):
        lo, hi = j * per, (j + 1) * per
        pieces = []
        for i, c in enumerate(chunks):
            s, e = int(starts[i]), int(starts[i + 1])
            if e <= lo or s >= hi:
                continue
            sl = [slice(None)] * c.ndim
            sl[axis] = slice(max(lo - s, 0), min(hi - s, e - s))
            pieces.append(c[tuple(sl)])
        out.append(pieces[0] if len(pieces) == 1
                   else np.concatenate(pieces, axis=axis))
    return out
