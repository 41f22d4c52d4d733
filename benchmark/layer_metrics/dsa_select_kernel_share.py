"""Model step: the share of the live rows whose top-``index_topk`` the Pallas
kernel ``dsa_select_topk`` selected (no sort), over the full layers of the
window's decode steps and prefill chunks: the program's own counters
``dsa_rows_kernel_selected`` / ``dsa_rows_selected`` (columns 6 and 7 of
the cache's last leaf), as the ``serving.decode_block`` and
``serving.prefill_chunk`` spans of the window's untraced part carry them.
1.0 on the chip; less means some shape fell back to ``lax.top_k``'s sort.
None where the program counts no such rows (before PR 38)."""
from benchmark.window_spans import by_name

SPANS = ("serving.decode_block", "serving.prefill_chunk")


def read(ctx):
    by = by_name(ctx) or {}
    carried = [sp.ids for name in SPANS for sp in by.get(name, ())
               if "dsa_rows_selected" in sp.ids]
    rows = sum(ids["dsa_rows_selected"] for ids in carried)
    if not rows:
        return None
    return sum(ids["dsa_rows_kernel_selected"] for ids in carried) / rows
