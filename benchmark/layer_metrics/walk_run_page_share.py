"""Kernels: the share of the live pages of the decode walks without a window
(``_walk_kernel``) that moved in run copies, 8 pages a copy: an aligned group
of 8 table columns that names consecutive blocks, rising or falling, and lies
wholly below the slot's length. The engine's own counters
``kv_pages_in_runs`` / ``kv_pages_live``, as the ``serving.decode_block``
spans of the window's untraced part carry them (counted on the host from each
slot's table row as it was armed). Near 1 where a pool hands each request
consecutive blocks; near 0 after churn. None where the program does not count
run pages."""
from benchmark.window_spans import by_name


def read(ctx):
    by = by_name(ctx) or {}
    carried = [sp.ids for sp in by.get("serving.decode_block", ())
               if "kv_pages_in_runs" in sp.ids]
    live = sum(ids["kv_pages_live"] for ids in carried)
    if not live:
        return None
    return sum(ids["kv_pages_in_runs"] for ids in carried) / live
