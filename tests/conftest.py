"""Test config: pin the suite to a virtual 8-device CPU platform BEFORE jax
initializes (the reference's analogue: CPU is the reference implementation,
SURVEY.md §4), and wait for async work between modules (reference:
conftest.py:61 `mx.nd.waitall()` between modules to catch async leakage)."""
import os

# Tests run with JAX_PLATFORMS=cpu — a plain environment variable, set here
# for runs that did not set it. Exception: MX_TPU_TESTS=1 keeps the chip
# visible ALONGSIDE the cpu so tests/test_tpu_consistency.py can compare the
# two backends on a TPU host (tpu first: it stays the default backend).
if os.environ.get("MX_TPU_TESTS") == "1":
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"
else:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags +
                                   " --xla_force_host_platform_device_count=8")
    # The suite compiles thousands of sub-second CPU programs: none would be
    # persisted (jax's 1 s threshold), each would pay the key hashing, and a
    # warm directory would make one run differ from the next. The package
    # still places the cache (tests/test_chip_smoke.py checks where).
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest  # noqa: E402

# Fast certification subset (`pytest -m quick`, <2 min on 1 vCPU): one
# representative test per subsystem so a judge/driver can certify the
# tree without the full 10-minute run. Centralized here instead of
# scattering markers across 60 files.
_QUICK = {
    "test_ndarray.py::test_creation",
    "test_autograd.py::test_record_flags",
    "test_gluon.py::test_parameter",
    "test_symbol.py::test_variable_and_compose",
    "test_ops.py::test_unary_vs_numpy",
    "test_kvstore_backends.py::test_custom_backend_create_and_roundtrip",
    "test_parallel.py::test_make_mesh",
    "test_optimizer.py::test_optimizer_decreases_quadratic",
    "test_optim_ops.py::test_sgd_update_out_semantics",
    "test_io_iters.py::test_csv_iter",
    "test_image.py::test_resize_and_crops",
    "test_partition.py::test_builtin_backends_registered",
    "test_probability.py::test_normal_log_prob_cdf_icdf",
    "test_profiler.py::test_record_op_from_funnel",
    "test_onnx.py::test_mlp_batchnorm_export",
    "test_control_flow.py::test_foreach_eager",
    "test_gpt.py::test_forward_shape_and_determinism",
    "test_estimator.py::test_estimator_fit_learns",
    "test_native.py::test_rtio_reader_matches_python",
    "test_model_store_artifact.py::test_packaged_artifact_resolves_and_verifies",
    "test_rnn_depth.py::test_rnn_layer_output_shape",
    "test_loss_metric_depth.py::test_l2_loss_value",
    "test_sparse.py::test_row_sparse_creation_and_densify",
    "test_quantization.py::test_entropy_threshold_clips_outliers",
    "test_graph_ops.py::test_edge_id",
    "test_contrib_ops_depth.py::test_quadratic",
    "test_legacy_ops_depth.py::test_slice_axis_reverse_crop",
    # static-analysis subsystem: whole-tree framework lint + auditor smoke
    # on a hybridized model_zoo block (ISSUE 1 CI gates)
    "test_analysis.py::test_framework_lint_tree_is_clean",
    "test_analysis.py::test_audit_hybridized_model_zoo_clean",
    # fault-tolerance subsystem (ISSUE 3 gates): worker-death + kvstore
    # retry suites, checkpoint fallback, and the chaos-convergence gate
    "test_fault.py::test_kvstore_push_retries_injected_fault",
    "test_fault.py::test_dataloader_worker_fault_retry",
    "test_fault.py::test_checkpoint_checksum_fallback",
    "test_fault.py::test_estimator_chaos_convergence",
    # serving subsystem (ISSUE 4 gates): stub-scheduler logic runs with
    # no XLA compile, so these certify backpressure/deadline/drain fast
    "test_serve.py::test_queue_backpressure_raises",
    "test_serve.py::test_deadline_expiry_classifies_retryable",
    "test_serve.py::test_drain_semantics_scheduler",
    "test_serve.py::test_serve_step_fault_seam",
    # paged serving (ISSUE 6 gates): allocator/prefix-cache host logic,
    # remaining-chunk SJF accounting, chunk/decode interleave, FL009 —
    # all stub-level, no XLA compile
    "test_serve.py::test_page_allocator_alloc_free_refcount",
    "test_serve.py::test_page_allocator_oom_loud",
    "test_serve.py::test_prefix_cache_register_lookup_evict",
    "test_serve.py::test_sjf_orders_by_remaining_prefill_chunks",
    "test_serve.py::test_chunked_prefill_interleaves_with_decode",
    "test_tools.py::test_fl009_tree_is_clean",
    "test_tools.py::test_fl007_tree_is_clean",
    # observability round 2 (ISSUE 5 gates): span tracer mechanics, one
    # trace per serve request (stub scheduler — no XLA), SLO burn math,
    # and the FL008 span-hygiene tree sweep
    "test_tracing.py::test_span_nesting_and_ids",
    "test_tracing.py::test_serve_request_trace_stub",
    "test_tracing.py::test_slo_latency_burn_math",
    "test_tools.py::test_fl008_tree_is_clean",
    # shardcheck (ISSUE 8 gates): spec-tier rule fixtures are pure host
    # math over avals (no trace, no compile) and the static meta-gate
    # runs framework lint + AST/eval_shape shardcheck over the tree
    "test_shardcheck.py::test_sc001_unconstrained_param_flagged",
    "test_shardcheck.py::test_sc002_divisibility_violation_flagged",
    "test_shardcheck.py::test_sc003_unknown_axis_flagged",
    "test_shardcheck.py::test_sc006_budget_exceeded_flagged",
    "test_shardcheck.py::test_rule_catalogue_complete",
    "test_shardcheck.py::test_static_gates_meta",
    "test_tools.py::test_fl010_tree_is_clean",
    # multi-tenant gateway (ISSUE 9 gates): WDRR fairness, preemption
    # with token survival, the deadline-while-preempted classification,
    # quota deferral, and the gateway fault seam — all stub-level, no
    # XLA compile — plus the FL011 boundedness tree sweep
    "test_gateway.py::test_wdrr_weighted_share",
    "test_gateway.py::test_preemption_resumes_with_tokens_intact",
    "test_gateway.py::test_preempted_deadline_expiry_classifies_retryable",
    "test_gateway.py::test_tenant_quota_defers_never_drops",
    "test_gateway.py::test_gateway_step_fault_seam",
    "test_tools.py::test_fl011_tree_is_clean",
    # compile & HBM observatory (ISSUE 10 gates): recompile forensics
    # on a tiny jit, census attribution (host-side sweep), the FL012
    # observatory-coverage tree sweep, and the bench trajectory gate on
    # the committed BENCH_r*.json history
    "test_telemetry_observatory.py::test_recompile_cause_shape",
    "test_telemetry_observatory.py::test_census_attribution_first_claim_and_weak_binding",
    "test_tools.py::test_fl012_tree_is_clean",
    "test_tools.py::test_bench_regress_green_on_committed_history",
    # fleet observability (ISSUE 12 gates): straggler z-score math,
    # chunked snapshot transport, collective_delay seam, clock-offset
    # stitching and flightrec merge on synthetic dumps, and the FL014
    # collective-hygiene tree sweep — all host-side, no multi-process
    "test_fleet.py::test_straggler_scores_slow_rank_wins",
    "test_fleet.py::test_exchange_large_chunks_past_command_slot",
    "test_fleet.py::test_collective_delay_sleeps_not_raises",
    "test_fleet.py::test_stitch_traces_rebases_by_clock_offset",
    "test_fleet.py::test_merge_flight_dumps_groups_by_rank",
    "test_tools.py::test_fl014_tree_is_clean",
    # kernel & goodput observatory (ISSUE 14 gates): roofline census
    # math + honest coverage on the committed fixture, the seeded
    # quantize-fusion diff, goodput lease/sum-to-wall semantics, the
    # kernelscope --demo render, and the FL016 series-index tree sweep
    "test_kernels.py::test_census_fixture_roofline_placement",
    "test_kernels.py::test_census_unknown_bytes_never_reads_fast",
    "test_kernels.py::test_diff_census_names_seeded_fusion",
    "test_kernels.py::test_goodput_states_sum_to_wall",
    "test_kernels.py::test_goodput_waterfall_renders_fixture",
    "test_kernels.py::test_kernelscope_demo_renders",
    "test_tools.py::test_fl016_tree_is_clean",
    # pod-scale sharded serving (ISSUE 15 gates): layout rule coverage,
    # 1-device-mesh parity with the unsharded engine, replica routing,
    # and the FL017 placement-provenance tree sweep — all host/CPU-mesh
    "test_sharded_serve.py::test_every_param_leaf_matches_exactly_one_rule",
    "test_sharded_serve.py::test_one_device_mesh_greedy_parity",
    "test_sharded_serve.py::test_router_prefers_warm_prefix_replica",
    "test_tools.py::test_fl017_tree_is_clean",
    # concurrency correctness (ISSUE 16 gates): the whole-tree static
    # racecheck sweep, the audited suspect seams, the ABBA the runtime
    # witness must catch without deadlocking, the by-construction
    # off-path guarantee, and the FL018 tracked-lock provenance sweep
    "test_racecheck.py::test_tree_static_sweep_is_clean",
    "test_racecheck.py::test_suspect_seam_analyzes_clean",
    "test_racecheck.py::test_abba_witnessed_without_deadlock",
    "test_racecheck.py::test_disarmed_tracked_lock_is_raw_primitive",
    "test_tools.py::test_fl018_tree_is_clean",
}


def pytest_collection_modifyitems(items):
    for item in items:
        key = f"{item.fspath.basename}::{item.name.split('[')[0]}"
        if key in _QUICK:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(autouse=True, scope="module")
def waitall_between_modules():
    yield
    import incubator_mxnet_tpu as mx

    mx.waitall()


@pytest.fixture(autouse=True, scope="module")
def own_serve_runner_between_modules():
    """`chipbench/control_eva.py` and `control_pangu.py` put their family's
    runner in `chipbench.runners.serve`'s place and leave it there: a module
    that runs after them in the same process (`control.py`'s own tests)
    would be handed another family's runner. Put the real one back."""
    import sys

    yield
    pkg, real = (sys.modules.get("chipbench.runners"),
                 sys.modules.get("chipbench.runners.serve"))
    if pkg is not None and real is not None:
        pkg.serve = real


@pytest.fixture(autouse=True)
def seed_rng():
    import numpy as onp

    import incubator_mxnet_tpu as mx

    onp.random.seed(0)
    mx.random.seed(0)
    yield


class _CountingTime:
    """`time`, counting the clock reads made through it."""

    CLOCKS = ("perf_counter", "perf_counter_ns", "monotonic", "time")

    def __init__(self):
        self.reads = 0

    def __getattr__(self, name):
        import time

        fn = getattr(time, name)
        if name not in self.CLOCKS:
            return fn

        def counted(*a):
            self.reads += 1
            return fn(*a)
        return counted


@pytest.fixture
def count_clock_reads(monkeypatch):
    """``clock = count_clock_reads(module)`` swaps the module's ``time`` for
    one that counts its clock reads (``clock.reads``) until the test ends:
    the structural form of "the off path is cheap" (a CPU run gives counts,
    not speeds)."""
    def swap(module):
        clock = _CountingTime()
        monkeypatch.setattr(module, "time", clock)
        return clock
    return swap
