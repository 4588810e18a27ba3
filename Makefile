# Test-suite splits mirroring the reference Makefile:25-60 (test_core /
# test_cli / test_big_modeling / test_fsdp / test_examples...), adapted to
# the TPU-native layout. All targets run on the virtual 8-device CPU mesh
# (tests/conftest.py forces it) — no hardware needed.

.PHONY: test test_core test_models test_parallel test_cli test_big_modeling test_checkpoint test_examples test_analysis test_slow lint lint-cold lint-sarif multichip telemetry-smoke resilience-smoke serve-smoke serve-chaos-smoke profile-smoke cache-smoke elastic-smoke autopilot-smoke kernel-smoke pipeline-smoke bench bench-gate

# graftlint: whole-program trace-safety & collective-correctness static
# analysis (docs/graftlint.md). Runs before the suite. The on-disk cache
# under .graftlint_cache/ (gitignored) makes the warm path sub-second;
# lint-cold deletes it first so CI measures the cold whole-program pass
# (budget: <15 s, asserted by tests/test_graftlint.py).
lint:
	python tools/graftlint.py accelerate_tpu/ --cache-dir .graftlint_cache

lint-cold:
	rm -rf .graftlint_cache
	python tools/graftlint.py accelerate_tpu/ --cache-dir .graftlint_cache

# SARIF smoke: emit the package report as SARIF (exit 0 expected — the
# package lints clean), structurally validate it, then run the validator's
# end-to-end self-test (known-bad fixture → graftlint subprocess → exit 1 →
# valid document with a fix hint). Chained into `make test` so a SARIF
# schema regression fails CI before any consumer sees it.
lint-sarif:
	mkdir -p .graftlint_cache
	python tools/graftlint.py accelerate_tpu/ --cache-dir .graftlint_cache \
	  --format sarif > .graftlint_cache/package.sarif
	python tools/sarif_check.py .graftlint_cache/package.sarif
	python tools/sarif_check.py --self-test

# dp>1 sharded-update proof on a DIFFERENT mesh extent than the default
# suite (which forces 8 virtual devices): ZeRO-1 numerics/memory/stability
# at dp=4, so a divisibility or reshard bug that happens to vanish at 8
# still fails CI (docs/zero1.md).  The compression suite rides along: the
# ISSUE acceptance row (int8/fp8/powersgd vs none at dp=4 — loss parity,
# 1/dp residual sharding, zero recompiles, ≥1.8x byte drop) runs here
# (docs/compression.md)
# the elastic-fleet suite rides along at dp=4: drain→vote→rollback
# rehearsal and the dp=4→dp=2 resize (bitwise state after reshard, zero
# recompiles after prewarm) exercise the exact multichip extent the
# acceptance row names (docs/elastic.md)
# the Pallas-kernel suite rides along at dp=4: interpreter-mode bitwise
# parity (ZeRO-1 ring gather, fused quantize+RS wire incl. residual
# evolution, paged decode), IR-inspection assertions, and the
# kernel-policy AOT fingerprint miss all exercise a real dp ring
# (docs/kernels.md)
# the ParallelPlan suite rides along at the ISSUE-15 acceptance geometry:
# 2-stage × dp=2 interleaved 1F1B with ZeRO-1 + int8 compression + grad
# accumulation in one captured step, ≤1e-3 loss parity vs the dp-only
# run, zero steady-state recompiles, warm AOT restart of the stage
# program with zero trace/compile (docs/parallel_plan.md)
multichip:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 python -m pytest \
	  tests/test_zero1.py tests/test_zero_sharding.py \
	  tests/test_compression.py tests/test_serving.py \
	  tests/test_serving_recovery.py tests/test_fleet.py \
	  tests/test_kernels.py tests/test_parallel_plan.py -q

# telemetry pipeline proof (docs/telemetry.md): tiny model, 3 steps + a
# forced shape change with telemetry + trace export on, JSONL validated
# through tools/telemetry_report.py (step phases present, recompile cause
# attributed), flight-ring health + trace tracks checked; then the
# injected-hang leg — a real 2-process gloo world where rank 1 hangs, the
# watchdog dumps both ranks and tools/blackbox_report.py must name the
# stalled rank and first divergent collective
telemetry-smoke:
	JAX_PLATFORMS=cpu python tools/telemetry_smoke.py

# preemption-path proof (docs/resilience.md): tiny model, injected SIGTERM
# at step 2, asserts the loop drains a COMPLETE checkpoint and a fresh
# accelerator resumes bitwise-equal to the uninterrupted run
resilience-smoke:
	JAX_PLATFORMS=cpu python tools/resilience_smoke.py

# serving-path proof (docs/serving.md): tiny GPT, 8 mixed-length staggered
# requests through the continuous-batching service on CPU — asserts every
# request's greedy tokens match a single-request generate(), zero recompile
# events after warmup (CompileWatcher forensics), no leaked KV blocks, and
# kind="serving" telemetry records present
serve-smoke:
	JAX_PLATFORMS=cpu python tools/serving_smoke.py

# fault-tolerant serving proof (docs/serving.md §fault tolerance): tiny
# GPT, staggered requests through a journaled replica with an injected
# transient decode fault and a mid-flight SIGTERM — asserts the fault is
# retried without a recompile, the drain leaves every open request in the
# journal, a restarted replica completes all of them bitwise-equal to
# generate() (zero lost), and the second pass against the same AOT store
# recovers with ZERO compiles
serve-chaos-smoke:
	JAX_PLATFORMS=cpu python tools/serve_chaos_smoke.py

# device-time proof (docs/telemetry.md): tiny GPT, 3 steps with every call
# profiled (profile_every_n=1) — asserts a nonempty per-device busy/idle +
# compute/collective split covering >= 80% of each replay's wall clock,
# a valid Prometheus scrape from the live metrics endpoint, and zero
# recompiles introduced by the profiling itself
profile-smoke:
	JAX_PLATFORMS=cpu python tools/profile_smoke.py

# zero-cold-start proof (docs/aot_cache.md): tiny GPT trained 2 steps in a
# fresh subprocess (miss → compile → store), then restarted in a SECOND
# fresh subprocess against the same cache dir — asserts the first captured
# call of the restart has zero trace/compile phase time (telemetry-
# verified), >= 1 cache hit, and bitwise-equal losses to the cold run
cache-smoke:
	JAX_PLATFORMS=cpu python tools/cache_smoke.py

# survive-and-resize proof (docs/elastic.md): tiny GPT on 4 virtual CPU
# devices, injected host_lost at step 2 — asserts drain → COMPLETE
# checkpoint → re-mesh dp=4→2 → reshard → loss-parity resume, run twice
# against one AOT store so the warm pass's post-resize step deserializes
# the prewarmed dp=2 program with zero trace/compile
elastic-smoke:
	JAX_PLATFORMS=cpu python tools/elastic_smoke.py

# closed-loop proof (docs/elastic.md §autopilot): tiny GPT on 4 virtual CPU
# devices, NO caller polling — injected host_lost → the autopilot shrinks
# dp 4→2 → injected host_gained → it grows back 2→4, losses within parity
# of an uninterrupted run, warm pass serves every post-resize build from
# the AOT store (zero trace/compile), and an injected signal_storm is
# suppressed by the debounce/hysteresis window (records, zero resizes)
autopilot-smoke:
	JAX_PLATFORMS=cpu python tools/autopilot_smoke.py

# pallas-kernel proof (docs/kernels.md): tiny GPT on 4 virtual CPU
# devices, every kernel armed under the interpreter — IR-inspection
# assertions (no unfused all-gather-then-dot, no full page-span
# materialization), loss-bitwise parity vs the reference paths, zero
# recompiles, paged decode token parity
kernel-smoke:
	JAX_PLATFORMS=cpu python tools/kernel_smoke.py

# parallel-plan proof (docs/parallel_plan.md): 2-stage × dp=2 interleaved
# 1F1B (V=2) with ZeRO-1 + int8 compression + grad accumulation in ONE
# captured step on 4 virtual CPU devices — asserts the resolved plan IS
# the acceptance geometry, ≤1e-3 loss parity vs the dp-only run, zero
# steady-state recompiles, interleaved-vs-fused trajectory parity, and
# the strictly-smaller analytic bubble at V=2
pipeline-smoke:
	JAX_PLATFORMS=cpu python tools/pipeline_smoke.py

# bench regression gate (docs/performance.md): diff the newest
# BENCH_r*.json primary step_ms against the previous round; exits nonzero
# past $$BENCH_REGRESSION_PCT (default 10, same-platform rows only) — a
# hot-path regression finally fails CI instead of riding the trajectory
bench-gate:
	python tools/bench_compare.py

test: lint lint-sarif multichip telemetry-smoke resilience-smoke serve-smoke serve-chaos-smoke profile-smoke cache-smoke elastic-smoke autopilot-smoke kernel-smoke pipeline-smoke bench-gate
	python -m pytest tests/ -q

test_core:
	python -m pytest tests/test_accelerator.py tests/test_state.py \
	  tests/test_operations.py tests/test_data_loader.py tests/test_native.py \
	  tests/test_data_loader_grid.py tests/test_num_workers.py \
	  tests/test_optimizer.py tests/test_optimizer_offload.py \
	  tests/test_capture_stability.py tests/test_aot_cache.py \
	  tests/test_precision.py \
	  tests/test_fp16_capture.py tests/test_autocast.py \
	  tests/test_comm_hook.py tests/test_powersgd.py \
	  tests/test_config_knobs.py \
	  tests/test_tracking.py tests/test_telemetry.py tests/test_device_time.py tests/test_spans_scopes.py \
	  tests/test_utils_misc.py tests/test_compile_cache_placement.py \
	  tests/test_no_fallback.py \
	  tests/test_deepspeed_compat.py tests/test_param_offload.py -q

test_models:
	python -m pytest tests/test_models.py tests/test_llama.py \
	  tests/test_llama_rope_scaling.py tests/test_chunked_ce.py \
	  tests/test_opt.py tests/test_gptj_neox.py tests/test_t5.py \
	  tests/test_generation.py tests/test_quantized_decode.py \
	  tests/test_moe.py tests/test_nemotron_h.py \
	  tests/test_torch_bridge.py tests/test_nn.py -q

test_parallel:
	python -m pytest tests/test_sharding_plan.py tests/test_zero_sharding.py \
	  tests/test_zero1.py tests/test_compression.py \
	  tests/test_pipeline.py tests/test_1f1b.py tests/test_parallel_plan.py \
	  tests/test_stagewise.py tests/test_ring_attention.py \
	  tests/test_flash_attention.py tests/test_sliding_window.py \
	  tests/test_tpu_compile.py -q

test_cli:
	python -m pytest tests/test_cli.py tests/test_menu.py tests/test_launcher.py \
	  tests/test_config_templates.py tests/test_chip_smoke.py -q

test_big_modeling:
	python -m pytest tests/test_big_modeling.py tests/test_hooks.py \
	  tests/test_offload.py tests/test_modeling_utils.py -q

test_checkpoint:
	python -m pytest tests/test_sharded_checkpoint.py tests/test_fsdp_utils.py \
	  tests/test_async_checkpoint.py tests/test_resilience.py \
	  tests/test_fleet.py tests/test_fleet_distributed.py -q

test_examples:
	python -m pytest tests/test_examples.py tests/test_external_scripts.py -q

test_analysis:
	python -m pytest tests/test_graftlint.py tests/test_outage_summary.py -q

# the slow split: subprocess launches + big compiles, partitioned out of
# the default suite by the `slow` marker; CI runs both targets
test_slow:
	RUN_SLOW=1 python -m pytest tests/ -q -m slow

bench:
	python bench.py
