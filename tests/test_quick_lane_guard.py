"""Lane guard for the paged-serving/pallas additions (tooling breaks
must surface as test failures, not as silently-skipped coverage). Pins
that

- every serving + paged-pallas test is COLLECTED by the quick lane
  (``-m 'not slow'``) — a stray ``slow`` mark or import error would
  otherwise drop the tier-1 bit-identity pins without failing CI;
- the interpret-mode pallas tests declare the pallas import guard so
  they SKIP (not error) on builds without Pallas;
- on the CPU lane the paged read takes the bit-identical reference
  path, never the kernel;
- the documents say what the tree holds: every ``PT_*`` name the program
  reads from the environment has its row in ONE table of
  ``docs/architecture.md``, and every repo path the documents write in
  backticks exists.
"""
import ast
import fnmatch
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GUARDED_FILES = ["tests/test_serving_paged.py", "tests/test_serving.py",
                 "tests/test_resilience.py", "tests/test_observability.py",
                 "tests/test_serving_tp.py", "tests/test_serving_spec.py",
                 "tests/test_serving_quant.py",
                 "tests/test_sparse_quant.py",
                 "tests/test_autotune.py",
                 "tests/test_frontend.py", "tests/test_fleet.py",
                 "tests/test_fleet_failover.py",
                 "tests/test_prefix_cache.py",
                 "tests/test_autoscaler.py",
                 "tests/test_durability.py"]

REQUIRED_NODES = [
    "test_serving_paged.py::TestPagedBitExactness::"
    "test_greedy_ragged_stream_bit_exact_one_compile",
    "test_serving_paged.py::TestPagedKernel::"
    "test_interpret_kernel_matches_reference",
    "test_serving_paged.py::TestInt8KV::"
    "test_write_path_error_within_runtime_bound",
    "test_serving.py::TestContinuousBatching::"
    "test_greedy_bit_exact_on_ragged_stream_one_compile",
    # PR 5 resilience pins: the chaos suite, the kill/restore
    # bit-identity contract, and the faults-disarmed inertness pin
    "test_resilience.py::TestSnapshotRestore::"
    "test_kill_restore_paged_bit_identical",
    "test_resilience.py::TestChaos::"
    "test_randomized_fault_schedules_hold_invariants",
    "test_resilience.py::TestInertWhenDisabled::"
    "test_disarmed_streams_bit_identical_compile_counts_pinned",
    # PR 6 observability pins: trace completeness under chaos, the
    # merged Perfetto artifact, the circuit-open flight dump, and the
    # profiler scheduler-gating regression
    "test_observability.py::TestRequestTraces::"
    "test_chaos_schedule_every_request_one_terminal",
    "test_observability.py::TestMergedChromeTrace::"
    "test_single_served_batch_trace_has_all_streams",
    "test_observability.py::TestFlightRecorder::"
    "test_dumps_on_circuit_open",
    "test_observability.py::TestProfilerSchedulerGating::"
    "test_closed_scheduler_keeps_host_ring_silent",
    # PR 7 tensor-parallel pins: dense + paged sharded bit-identity on
    # the simulated 2x4 mesh, the seeded-sampling parity, the int8-hop
    # queryable bound, and the AOT 4/5-output arity compatibility
    "test_serving_tp.py::TestDenseTPParity::"
    "test_greedy_staggered_bit_exact_one_compile",
    "test_serving_tp.py::TestDenseTPParity::"
    "test_seeded_sampling_bit_exact",
    "test_serving_tp.py::TestPagedTPParity::"
    "test_greedy_staggered_bit_exact_one_compile",
    "test_serving_tp.py::TestPsumInt8::"
    "test_int8_bound_queryable_from_live_state",
    "test_serving.py::TestDecodeBlockArity::"
    "test_legacy_four_output_stream_bit_identical",
    # PR 8 speculative-decoding pins: dense + paged/chunked bit-identity
    # with the verify-block compile count, the eos-mid-span acceptance
    # cut, the k=0 degenerate window, the chaos schedule with spec
    # enabled, and the mid-stream kill/restore round trip
    "test_serving_spec.py::TestSpecBitExactness::"
    "test_dense_greedy_stream_bit_exact_one_compile",
    "test_serving_spec.py::TestSpecBitExactness::"
    "test_paged_chunked_stream_bit_exact_one_compile",
    "test_serving_spec.py::TestAcceptance::"
    "test_eos_inside_accepted_span",
    "test_serving_spec.py::TestAcceptance::"
    "test_k0_degenerates_to_plain_decode",
    "test_serving_spec.py::TestSpecResilience::"
    "test_chaos_schedule_with_spec_holds_invariants",
    "test_serving_spec.py::TestSpecResilience::"
    "test_kill_restore_mid_stream_bit_identical",
    # PR 8 carried follow-ups: the artifact-identity snapshot gate and
    # the paged-artifact stub routing pin
    "test_serving.py::TestArtifactSnapshotIdentity::"
    "test_stub_kill_restore_round_trip",
    "test_serving_paged.py::TestPagedArtifact::"
    "test_stub_paged_backend_routes_and_serves",
    # PR 10 bandwidth-true quantization pins: in-read int8-KV parity
    # vs the dequant-then-dense oracle (kernel interpret + CPU
    # fallback), the no-dense-fp32-KV-transient jaxpr walk, the
    # weight-quant bit-identity-to-dequantized-twin contract, and the
    # quant routing matrix
    "test_serving_quant.py::TestInt8KVInRead::"
    "test_interpret_kernel_matches_oracle",
    "test_serving_quant.py::TestInt8KVInRead::"
    "test_cpu_fallback_matches_oracle",
    "test_serving_quant.py::TestInt8KVInRead::"
    "test_quantized_decode_holds_no_dense_fp32_kv",
    "test_serving_quant.py::TestInt8KVInRead::"
    "test_int8_engine_stream_matches_oracle_route",
    "test_serving_quant.py::TestWeightOnlyServing::"
    "test_int8_dense_stream_bit_identical_to_dequant_twin",
    "test_serving_quant.py::TestWeightOnlyServing::"
    "test_paged_kv_int8_plus_weight_int8",
    "test_serving_quant.py::TestQuantRouting::"
    "test_env_flag_never_reroutes_explicit_backend",
    "test_sparse_quant.py::TestWeightOnlyQuant::"
    "test_grouped_roundtrip_and_linear",
    # the six compositions the engine factory builds, over one stream
    # (PR 29: the safety net under the dense/paged/spec class fork)
    "test_serving_quant.py::"
    "test_every_composition_serves_the_same_stream[dense]",
    "test_serving_quant.py::"
    "test_every_composition_serves_the_same_stream[paged-kv_int8-w_int8]",
    "test_serving_quant.py::"
    "test_every_composition_serves_the_same_stream[paged-spec_k8]",
    # PR 12 autotuner pins: the staleness/consumer contracts
    "test_autotune.py::TestTable::test_stale_stamp_refused_and_warned",
    "test_autotune.py::TestConsumers::"
    "test_xent_chunk_default_unchanged_without_table",
    "test_autotune.py::TestConsumers::"
    "test_flash_block_pref_resolution_order",
    # PR 13 front-door pins: streaming bit-identity (dense + paged),
    # the preempt-resume bit-identity matrix (greedy AND seeded-
    # sampled), the span-events-never-terminals trace contract, the
    # resume-state snapshot round trip, WFQ shares, and the chaos
    # schedule with preemption + WFQ active
    "test_frontend.py::TestStreaming::"
    "test_iterator_greedy_bit_identical[dense]",
    "test_frontend.py::TestStreaming::"
    "test_iterator_greedy_bit_identical[paged]",
    "test_frontend.py::TestPreemption::"
    "test_greedy_preempt_resume_bit_identical[paged]",
    "test_frontend.py::TestPreemption::"
    "test_seeded_sampled_preempt_resume_bit_identical[dense]",
    "test_frontend.py::TestPreemption::"
    "test_seeded_sampled_preempt_resume_bit_identical[paged]",
    "test_frontend.py::TestPreemption::"
    "test_preempt_resume_are_span_events_one_terminal",
    "test_frontend.py::TestPreemption::"
    "test_preempted_request_survives_snapshot_restore",
    "test_frontend.py::TestFairScheduler::"
    "test_weighted_shares_over_backlog",
    "test_frontend.py::TestFrontdoorChaos::"
    "test_chaos_with_preemption_and_wfq",
    # PR 14 disaggregated-fleet pins: cross-worker bit-identity
    # (greedy + seeded-sampled, dense + paged + paged+kv_int8), the
    # bytes-true int8 wire format, the prefix-affinity fleet-wide
    # cache gate, live decode-worker migration, and the chaos schedule
    # over the handoff fault sites with zero leaks on both arenas
    "test_fleet.py::TestFleetBitIdentity::"
    "test_paged_greedy_staggered_bit_identical_one_compile",
    "test_fleet.py::TestFleetBitIdentity::"
    "test_paged_seeded_sampled_bit_identical",
    "test_fleet.py::TestFleetBitIdentity::"
    "test_dense_greedy_and_sampled_bit_identical",
    "test_fleet.py::TestFleetBitIdentity::"
    "test_paged_kv_int8_bit_identical",
    "test_fleet.py::TestWireFormat::"
    "test_int8_payload_ships_codes_never_dequantized",
    "test_fleet.py::TestRouter::"
    "test_fleet_wide_prefix_cache_via_affinity",
    "test_fleet.py::TestFleetResilience::"
    "test_chaos_handoff_sites_hold_invariants",
    "test_fleet.py::TestMigrationAndScale::"
    "test_decode_worker_live_migration_bit_identical",
    # PR 14 satellites: preemption composes with spec engines
    # (bit-identical resumes), and stream delivered-offsets ride
    # snapshots (kill/restore/re-attach sees only unseen tokens)
    "test_serving_spec.py::TestSpecPreemption::"
    "test_greedy_preempt_resume_bit_identical[dense]",
    "test_serving_spec.py::TestSpecPreemption::"
    "test_greedy_preempt_resume_bit_identical[paged]",
    "test_serving_spec.py::TestSpecPreemption::"
    "test_seeded_sampled_preempt_resume_bit_identical",
    "test_frontend.py::TestStreamRestore::"
    "test_kill_restore_reattach_sees_only_unseen_tokens",
    # PR 15 failure-domain pins: the socket transport's at-least-once
    # duplicate delivery, adoption idempotency at exact refcounts, the
    # tampered-CRC pre-allocation refusal, the fault-site table guard,
    # and the headline kill-mid-decode redrive bit-identity matrix
    # (paged under ~1% wire faults + one-terminal trace, dense,
    # paged+kv_int8) plus the explicit worker_lost endgame
    "test_fleet_failover.py::TestSocketTransport::"
    "test_disconnect_before_ack_delivers_duplicate",
    "test_fleet_failover.py::TestAdoptIdempotency::"
    "test_duplicate_adopt_is_noop_at_exact_refcounts",
    "test_fleet_failover.py::TestAdoptIdempotency::"
    "test_tampered_crc_refused_before_any_allocation",
    "test_fleet_failover.py::TestFaultSiteTable::"
    "test_every_armed_site_appears_in_the_docstring_table",
    "test_fleet_failover.py::TestPrefillRedriveResume::"
    "test_user_preemption_resume_still_refused",
    "test_fleet_failover.py::TestRedriveBitIdentity::"
    "test_paged_kill_mid_decode_bit_identical_under_wire_faults",
    "test_fleet_failover.py::TestRedriveBitIdentity::"
    "test_dense_kill_mid_decode_bit_identical",
    "test_fleet_failover.py::TestRedriveBitIdentity::"
    "test_paged_kv_int8_kill_bit_identical",
    "test_fleet_failover.py::TestRedriveBitIdentity::"
    "test_no_surviving_decode_worker_fails_explicitly",
    # PR 16 fleet-prefix-cache pins: the headline remote-fetch
    # bit-identity matrix (greedy + sampled, with compile counts),
    # the watermark-eviction directory retraction, the dead-owner
    # local-prefill fallback + lease expiry, and the chaos schedule
    # over the new fetch/directory fault sites
    "test_prefix_cache.py::TestRemoteFetchBitIdentity::"
    "test_greedy_and_sampled_remote_fetch_bit_identical",
    "test_prefix_cache.py::TestRemoteFetchBitIdentity::"
    "test_kv_int8_remote_fetch_bit_identical",
    "test_prefix_cache.py::TestEvictionTier::"
    "test_watermark_eviction_retracts_directory",
    "test_prefix_cache.py::TestFailureSemantics::"
    "test_dead_owner_falls_back_then_lease_expires_entries",
    "test_prefix_cache.py::TestFailureSemantics::"
    "test_chaos_fetch_sites_hold_invariants",
    "test_serving_paged.py::TestPrefixSharing::"
    "test_decode_time_block_sharing_extends_the_chain",
    # PR 17 autoscaling pins: the deterministic trace generator's
    # byte-identical replay + per-component stream independence, the
    # decision kernel's hysteresis/cooldown/below-min contracts, the
    # cost-aware prefix eviction, and the headline kill-and-burst
    # matrix (autoscaled streams bit-identical to the static fleet,
    # paged + paged+kv_int8, nothing ever recompiles)
    "test_autoscaler.py::TestLoadgen::test_byte_identical_replay",
    "test_autoscaler.py::TestLoadgen::"
    "test_component_stream_independence",
    "test_autoscaler.py::TestRecentQuantile::test_window_semantics",
    "test_autoscaler.py::TestCostAwareEviction::"
    "test_reused_prefix_outlives_cold_chain",
    "test_autoscaler.py::TestDecisionKernel::"
    "test_up_cooldown_suppresses_thrash",
    "test_autoscaler.py::TestDecisionKernel::"
    "test_lease_death_bypasses_cooldown",
    "test_autoscaler.py::TestAutoscalerOnFleet::"
    "test_scale_action_retries_under_faults",
    "test_autoscaler.py::TestAutoscaleKillBurst::test_paged",
    "test_autoscaler.py::TestAutoscaleKillBurst::test_paged_kv_int8",
    "test_durability.py::TestJournal::"
    "test_torn_tail_truncated_loudly",
    "test_durability.py::TestWholeFleetRecovery::"
    "test_paged_recover_bit_identical_greedy_and_sampled",
    "test_durability.py::TestWholeFleetRecovery::"
    "test_kv_int8_recover_bit_identical",
    "test_durability.py::TestWholeFleetRecovery::"
    "test_torn_tail_recovery_is_loud_and_bit_identical",
    "test_durability.py::TestSpillTier::"
    "test_watermark_eviction_spills_then_spill_hit",
]


def test_serving_tests_collected_in_quick_lane():
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", *GUARDED_FILES],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    # returncode 0 == zero collection errors (pytest exits 2 on any)
    assert p.returncode == 0, (p.stdout[-1500:], p.stderr[-800:])
    for node in REQUIRED_NODES:
        assert node in p.stdout, f"quick lane lost {node}"


def test_interpret_tests_guard_pallas_import():
    # the kernel tests must skip cleanly on a build without Pallas:
    # the class exercising interpret mode has to declare importorskip
    src = open(os.path.join(ROOT, "tests", "test_serving_paged.py")).read()
    kernel_tests = src.split("class TestPagedKernel")[1]
    assert 'importorskip("jax.experimental.pallas")' in kernel_tests


def test_cpu_lane_never_dispatches_paged_kernel():
    import paddle_tpu.ops.pallas.fused as fused
    from paddle_tpu.ops.pallas.paged_attention import _kernel_ok
    if jax.default_backend() != "cpu":
        return                       # on-hardware lane: kernel allowed
    assert not fused._FORCE_INTERPRET     # test isolation sanity
    assert not _kernel_ok(jnp.zeros((4, 8, 2, 16), jnp.float32))


# -- the documents against the tree ------------------------------------------

def _read(*parts) -> str:
    with open(os.path.join(ROOT, *parts)) as f:
        return f.read()


def test_every_env_knob_has_its_row_in_the_one_table():
    """The ``PT_*`` names in ``paddle_tpu/``'s source (whole string
    constants: what ``utils.flags.env_*`` and ``os.environ`` are handed,
    directly or through a helper; metric and counter names are lower
    case) are the first column of the table under "Environment knobs" in
    docs/architecture.md, no more and no fewer."""
    read = set()
    for path in glob.glob(os.path.join(ROOT, "paddle_tpu", "**", "*.py"),
                          recursive=True):
        for node in ast.walk(ast.parse(_read(path))):
            if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                    and re.fullmatch(r"PT_[A-Z0-9_]+", node.value):
                read.add(node.value)
    doc = _read("docs", "architecture.md")
    section = doc.split("\n## Environment knobs\n")[1].split("\n## ")[0]
    rows = re.findall(r"^\| `(PT_[A-Z0-9_]+)` \|", section, re.M)
    assert len(rows) == len(set(rows)), "a knob has two rows"
    assert set(rows) == read, (
        f"read but not in the table: {sorted(read - set(rows))}; "
        f"in the table but not read: {sorted(set(rows) - read)}")
    assert len(read) > 40                  # the scan still finds them


_DOC_FILE = (".py", ".md", ".sh", ".json", ".jsonl", ".txt", ".cc")


def _tree_files() -> list:
    """What git would commit, near enough: no hidden or cache directory,
    and no top-level ``_scratch`` copy (.gitignore lists them)."""
    out = []
    for base, dirs, files in os.walk(ROOT):
        top = base == ROOT
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "__pycache__"
                   and d != "chiprun_out" and not (top and d.startswith("_"))]
        out += [os.path.relpath(os.path.join(base, f), ROOT) for f in files]
    return out


def _expand(word: str) -> list:
    m = re.search(r"\{([^{}]*,[^{}]*)\}", word)
    if not m:
        return [word]
    return [w for alt in m.group(1).split(",")
            for w in _expand(word[:m.start()] + alt + word[m.end():])]


def _doc_paths(text: str):
    """File paths inside backticks: a word that ends in a source or
    record suffix, line numbers stripped, brace lists expanded. Words
    with a placeholder (``<cell>``, ``{epoch}``, ``...``), a home or
    absolute path, or an option are not the tree's."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = re.sub(r":\d+(-\d+)?$", "", word.strip(".,;:()"))
            if not word.endswith(_DOC_FILE) or "..." in word \
                    or re.search(r"[<>$~=]|^[/-]", word) \
                    or not re.fullmatch(r"[\w.{},*/-]+", word):
                continue
            yield from (w for w in _expand(word) if "{" not in w)


def test_every_repo_path_the_documents_name_exists():
    """README.md, docs/architecture.md and PERF.md section 3 (the layer
    map): a path in backticks is a file of the tree, by its whole path or
    by the tail the prose shortens it to (``serving/paging.py``,
    ``test_sot.py``); a glob matches at least one."""
    files = _tree_files()
    perf = _read("PERF.md")
    docs = {"README.md": _read("README.md"),
            "docs/architecture.md": _read("docs", "architecture.md"),
            "PERF.md section 3": perf[perf.index("\n## 3"):
                                      perf.index("\n## 4")]}
    missing, checked = [], 0
    for name, text in docs.items():
        for path in sorted(set(_doc_paths(text))):
            checked += 1
            if not any(fnmatch.fnmatchcase(f, path)
                       or fnmatch.fnmatchcase(f, "*/" + path)
                       for f in files):
                missing.append(f"{name}: {path}")
    assert not missing, missing
    assert checked > 100                   # the scan still finds them
