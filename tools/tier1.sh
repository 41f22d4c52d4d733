#!/usr/bin/env bash
# Tier-1 verify runner (the ROADMAP.md command, with a paper trail).
#
# Adds what the raw command doesn't record:
#   - jax/jaxlib versions stamped next to the results (the per-re-anchor
#     jaxlib-upgrade check needs to know which jaxlib produced each run);
#   - the known environment landmine printed up front: jax's persistent
#     compile cache + pytest xdist/randomly corrupts the native heap
#     when a SECOND paged step backend compiles in one process (glibc
#     double-free at exit; documented in tests/test_resilience.py).
#     This invocation passes `-p no:xdist -p no:randomly` and is immune
#     — re-check the landmine on every jaxlib upgrade.
#   - a stale-cache guard: a .jax_cache accumulated across MANY
#     sessions (~140 entries, PR 7 data point) reproducibly segfaults
#     the full suite mid-GC at a later paged-backend jax.jit even with
#     the plugins disabled. Entry-count/age heuristic below wipes it
#     BEFORE the run instead of after the crash.
#
# Usage: tools/tier1.sh [extra pytest args]
# Log:   /tmp/_t1.log (flat), DOTS_PASSED echoed at the end.
set -o pipefail
cd "$(dirname "$0")/.."

# --- stale multi-session compile cache (ROADMAP heap-corruption
# landmine): wipe when the entry count says "many sessions" or the
# oldest entry says "not from today's session". A fresh worktree starts
# cache-empty, which is why seed-comparison runs never crash.
CACHE=".jax_cache"
CACHE_MAX_ENTRIES="${TIER1_CACHE_MAX_ENTRIES:-100}"
CACHE_MAX_AGE_H="${TIER1_CACHE_MAX_AGE_H:-24}"
if [ -d "$CACHE" ]; then
  n=$(find "$CACHE" -type f 2>/dev/null | wc -l)
  oldest=$(find "$CACHE" -type f -printf '%T@\n' 2>/dev/null \
           | sort -n | head -1 | cut -d. -f1)
  age_h=0
  if [ -n "$oldest" ]; then
    age_h=$(( ($(date +%s) - oldest) / 3600 ))
  fi
  if [ "$n" -gt "$CACHE_MAX_ENTRIES" ] || \
     [ "$age_h" -gt "$CACHE_MAX_AGE_H" ]; then
    echo "tier1: wiping stale $CACHE ($n entries, oldest ${age_h}h old" \
         "> ${CACHE_MAX_ENTRIES}/${CACHE_MAX_AGE_H}h) — multi-session" \
         "accumulation corrupts the native heap mid-GC (ROADMAP note)"
    rm -rf "$CACHE"
  else
    echo "tier1: $CACHE ok ($n entries, oldest ${age_h}h old)"
  fi
fi

VERS=$(JAX_PLATFORMS=cpu python - <<'EOF'
import importlib.metadata as md
def v(p):
    try:
        return md.version(p)
    except md.PackageNotFoundError:
        return "unknown"
print(f"jax={v('jax')} jaxlib={v('jaxlib')}")
EOF
)
echo "tier1: $VERS"
echo "tier1: re-anchor check — re-verify the compile-cache landmine on" \
     "any jaxlib upgrade from the version above (ROADMAP env note)"
echo "tier1: landmine note — persistent compile cache + xdist/randomly" \
     "corrupts the native heap on a 2nd in-process paged-backend" \
     "compile; this runner passes -p no:xdist -p no:randomly (immune)." \
     "A STALE multi-session .jax_cache can still segfault the" \
     "full suite mid-GC: on a native crash, rm -rf .jax_cache" \
     "and re-run before blaming the tree. Re-check on each jaxlib" \
     "upgrade (ROADMAP env note)."

# --- autotune tuning-table provenance: kernels consult the table at
# trace time (ops/pallas/autotune.py); a stamp that disagrees with the
# running jaxlib/device kind is refused by lookup() — surface the same
# verdict here instead of letting stale block shapes pass silently.
TUNE_TABLE="${PT_TUNE_TABLE:-$HOME/.cache/paddle_tpu/tune_table.json}"
if [ -f "$TUNE_TABLE" ]; then
  JAX_PLATFORMS=cpu PT_TUNE_TABLE="$TUNE_TABLE" python - <<'EOF'
from paddle_tpu.ops.pallas import autotune as at
path = at.table_path()
table = at.load_table(path)
if table is None:
    print(f"tier1: WARNING autotune table {path} unreadable — kernels "
          "fall back to documented defaults")
else:
    ok, reason = at.stamp_matches(table.get("stamp", {}))
    n = len(table.get("entries", {}))
    if ok:
        print(f"tier1: autotune table ok ({path}, {n} entries, stamp "
              f"{table['stamp'].get('jaxlib_version')}/"
              f"{table['stamp'].get('device_kind')})")
    else:
        print(f"tier1: WARNING autotune table {path} is STALE "
              f"({reason}) — kernels fall back to documented defaults; "
              "re-run autotune.run_autotune to refresh")
EOF
else
  echo "tier1: no autotune table at $TUNE_TABLE (kernels use" \
       "documented default block shapes; autotune.run_autotune" \
       "writes one)"
fi

rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly "$@" 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "tier1: $VERS" >> /tmp/_t1.log
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
exit $rc
