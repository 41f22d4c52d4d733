"""Device seconds of the operations under a NAMED SCOPE, for what is not a
named Pallas call.

``trace_kernels`` sums events by the start of their name, which finds a
Pallas call (``dsa_index_scores_decode.3``) but not what XLA made of a
``lax.top_k`` or a gather: those are ``sort.42`` / ``fusion.1079`` on the
"XLA Ops" line, and the device trace keeps nothing of where they came from
(an event's statistics are its offset and duration). What ties an
instruction to the program is the ``op_name`` in its metadata in the
COMPILED program's text (``jit(block_fn)/while/body/closed_call/attn/
dsa_select/top_k``). So this takes both: the text of the one program asked
about, and what ``trace_reduce.load`` loaded; it keeps the operations that
ran INSIDE an execution of that program (instruction names repeat across
programs; the "XLA Modules" line says when each ran) and sums, per scope,
the SELF time of those whose ``op_name`` holds the scope as a path
component, averaged over the device planes. A scope nothing ran under is
absent, and the metric that divides by it is left out of the line.
"""
from __future__ import annotations

import bisect
import re

from .trace_reduce import self_times, short_name

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"",
    re.M)


def scopes_of_program(text: str, scopes) -> dict:
    """``{instruction name: scope}`` for the instructions of a compiled
    program's text whose ``op_name`` names one of ``scopes``."""
    out = {}
    for name, op_name in _INSTRUCTION.findall(text):
        parts = set(op_name.split("/"))
        for scope in scopes:
            if scope in parts:
                out[name] = scope
                break
    return out


def seconds_by_scope(loaded: dict, module: str, text: str, scopes) -> dict:
    """``{scope: (events, device seconds)}`` over ``loaded["devices"]`` for
    the program ``module`` (its short name on the "XLA Modules" line) whose
    compiled text is ``text``."""
    named = scopes_of_program(text, scopes)
    devs = loaded.get("devices") or {}
    totals = {}
    for d in devs.values():
        runs = sorted((s, s + dur) for name, s, dur in d["modules"]
                      if short_name(name) == module)
        starts = [s for s, _ in runs]
        found = []
        for name, s, dur in d["ops"]:
            scope = named.get(short_name(name))
            i = bisect.bisect_right(starts, s) - 1
            if scope is not None and i >= 0 and s < runs[i][1]:
                found.append((scope, s, dur))
        for scope, _, own in self_times(found):
            t = totals.setdefault(scope, [0, 0.0])
            t[0] += 1
            t[1] += own / 1e9
    n = max(1, len(devs))
    return {s: (c // n, sec / n) for s, (c, sec) in totals.items()}
