# Test-suite splits mirroring the reference Makefile:25-60 (test_core /
# test_cli / test_big_modeling / test_fsdp / test_examples...), adapted to
# the TPU-native layout. All targets run on the virtual 8-device CPU mesh
# (tests/conftest.py forces it) — no hardware needed.

.PHONY: test test_core test_models test_parallel test_cli test_big_modeling test_checkpoint test_examples test_analysis test_slow lint lint-cold lint-sarif multichip telemetry-smoke pipeline-smoke

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

# the injected-hang proof (docs/telemetry.md), the one leg of the telemetry
# pipeline that needs two real processes: a 2-process gloo world where rank
# 1 hangs, the watchdog dumps both ranks and tools/blackbox_report.py must
# name the stalled rank and first divergent collective.  What one process
# can show is in tests/test_telemetry.py
telemetry-smoke:
	JAX_PLATFORMS=cpu python tools/telemetry_smoke.py

# per-stage captured programs across a restart (docs/parallel_plan.md): two
# fresh one-device subprocesses against one AOT store, the warm one loading
# all 2·S·V programs with zero compiles at a bitwise-equal loss.  A script
# until the stagewise loader pins its devices (ROADMAP D9); the rest of the
# plan's acceptance is in tests/test_parallel_plan.py and tests/test_1f1b.py
pipeline-smoke:
	JAX_PLATFORMS=cpu python tools/pipeline_smoke.py

test: lint lint-sarif multichip telemetry-smoke pipeline-smoke
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
	  tests/test_tracking.py tests/test_telemetry.py tests/test_device_time.py tests/test_spans_scopes.py tests/test_compile_spans.py \
	  tests/test_utils_misc.py tests/test_compile_cache_placement.py \
	  tests/test_no_fallback.py \
	  tests/test_deepspeed_compat.py tests/test_param_offload.py -q

test_models:
	python -m pytest tests/test_models.py tests/test_llama.py \
	  tests/test_llama_rope_scaling.py tests/test_chunked_ce.py \
	  tests/test_opt.py tests/test_gptj_neox.py tests/test_t5.py \
	  tests/test_generation.py tests/test_quantized_decode.py \
	  tests/test_moe.py tests/test_nemotron_h.py tests/test_olmo_hybrid.py \
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
	python -m pytest tests/test_graftlint.py -q

# the slow split: subprocess launches + big compiles, partitioned out of
# the default suite by the `slow` marker; CI runs both targets
test_slow:
	RUN_SLOW=1 python -m pytest tests/ -q -m slow
