"""Benchmark: GPT-2-small causal-LM training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...diag}.

The flagship workload (BASELINE.md): transformer training throughput,
bf16, full captured step (fwd+bwd+AdamW fused into one XLA program).
``vs_baseline`` compares per-chip tokens/sec against an 8×A100 NCCL DDP
baseline estimate for GPT-2-small of 150k tokens/s/GPU (A100 312 TFLOP/s
bf16 at ~40% MFU over ~6N FLOPs/token; BASELINE.json publishes no number,
so the denominator is this documented estimate).

What it prints is true of the device it names: a run lands on ``tpu`` or,
asked with ``--cpu``, rehearses the same code at tiny sizes on the CPU and
says so — under metric names without ``per_chip``, with no MFU.  Any other
device, an unknown ``device_kind`` or a failed phase exits non-zero.  All
MFU/geometry/diagnostic fields land in the JSON itself, not stderr.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

A100_BASELINE_TOKENS_PER_SEC = 150_000.0
# bf16 peak FLOP/s of one chip, keyed by jax's ``device_kind``, with its
# source.  A device that is not here is an error, not a default: an MFU
# against another chip's peak is a wrong number.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": (197e12, 'Google Cloud documentation, "TPU v5e"'),
}
TPU_PEAK_FLOPS = None  # this run's device's peak; main() sets it from the table


def peak_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind][0]
    except KeyError:
        raise SystemExit(
            f"bench: no published peak for device_kind {device_kind!r}; add it "
            f"to PEAK_BF16_FLOPS with its source (known: {sorted(PEAK_BF16_FLOPS)})"
        ) from None

# PER-CHIP batch; the global batch is BATCH * n_devices so it always
# shards evenly over the dp axis.  12/chip measured fastest on v5e for
# GPT-2-small at seq 1024 (49.6% MFU vs 47.8% at 8, 47.0% at 16 —
# 12288-row matmuls tile the MXU best)
BATCH = int(os.environ.get("BENCH_BATCH", 12))
SEQ = int(os.environ.get("BENCH_SEQ", 1024))
STEPS = int(os.environ.get("BENCH_STEPS", 50))
WARMUP = int(os.environ.get("BENCH_WARMUP", 5))
# whole-run deadline.  BENCH_FULL runs carry ~6 extra workloads with multi-minute cold compiles
# (the window A/B alone compiles an 8-layer Llama at seq 8192 twice), so
# their default budget is larger; the plain driver run keeps 1800.
TOTAL_TIMEOUT_S = float(
    os.environ.get(
        "BENCH_TOTAL_TIMEOUT", 4800 if os.environ.get("BENCH_FULL") == "1" else 1800
    )
)


_PRIMARY_RESULT: dict = {}
_ON_ACCEL = False  # main() sets it once the device is known
# exactly-one-result-line guard: the watchdog timer thread and the main
# thread race to emit when extras finish right at the deadline — whoever
# takes the lock first prints; the loser stays silent
_EMIT_LOCK = threading.Lock()
_EMITTED = False


def _emit_once(payload: dict) -> bool:
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return False
        _EMITTED = True
        print(json.dumps(payload), flush=True)
        return True


_DEADLINE_AT: float = float("inf")


def _remaining_s() -> float:
    return _DEADLINE_AT - time.monotonic()


def _persist_partial(result: dict) -> None:
    """Write the accumulated rows after every workload: a deadline cut
    keeps every completed row on disk."""
    path = os.environ.get("BENCH_PARTIAL_PATH", "BENCH_partial.json")
    try:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1, sort_keys=True)
            f.write("\n")
    except OSError:
        pass


def _record_failure(out: dict, key: str, exc: BaseException) -> None:
    """A phase that raised: its row says so (``<key>_error``), the traceback
    goes to stderr, and main() turns any such key into a non-zero exit —
    the other phases still run and report, but the run did not pass."""
    import traceback

    traceback.print_exception(type(exc), exc, exc.__traceback__, file=sys.stderr)
    out[f"{key}_error"] = f"{type(exc).__name__}: {exc}"[:300]


def _arm_deadline() -> None:
    global _DEADLINE_AT
    _DEADLINE_AT = time.monotonic() + TOTAL_TIMEOUT_S

    def _expire():
        # a cut run did not pass, whatever it managed to measure: report the
        # rows that finished (named for the device they ran on), exit non-zero
        out = dict(_PRIMARY_RESULT)
        out["deadline_error"] = (
            f"cut at BENCH_TOTAL_TIMEOUT={TOTAL_TIMEOUT_S:.0f}s"
            + ("" if _PRIMARY_RESULT else " before the primary workload finished")
        )
        out.setdefault("metric", "gpt2_small_train_tokens_per_sec_per_chip")
        _emit_result(out, _ON_ACCEL)
        os._exit(1)

    t = threading.Timer(TOTAL_TIMEOUT_S, _expire)
    t.daemon = True
    t.start()


def _bert_mrpc_workload(on_accel: bool) -> dict:
    """BASELINE.md's headline metric: BERT-base MRPC-style samples/sec/chip.

    Mirrors examples/nlp_example.py geometry (batch 32, seq padded to 128 —
    reference examples/nlp_example.py:81) on synthetic token ids; the metric
    is throughput, which does not depend on the text being real.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import BertConfig, BertForSequenceClassification

    nn.manual_seed(0)
    # fresh Accelerator: its captured step must carry BERT state only, not
    # the primary workload's 124M GPT params (model registry is per-instance)
    acc = Accelerator(mixed_precision="bf16")
    cfg = BertConfig.base() if on_accel else BertConfig.small()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    model = BertForSequenceClassification(cfg)
    opt = optim.AdamW(model.parameters(), lr=2e-5)
    model, opt = acc.prepare(model, opt)

    batch, seq, steps = (32, 128, 30) if on_accel else (4, 32, 3)

    def step_fn(ids, labels):
        opt.zero_grad()
        out = model(ids, labels=labels)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    rng = np.random.default_rng(0)
    ids = batch_to_global_array(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)),
        mesh=acc.mesh,
    )
    labels = batch_to_global_array(
        jnp.asarray(rng.integers(0, 2, (batch,), dtype=np.int32)), mesh=acc.mesh
    )
    t0 = _time.perf_counter()
    float(step(ids, labels))
    compile_s = _time.perf_counter() - t0
    for _ in range(4):
        step(ids, labels)
    float(step(ids, labels))
    t0 = _time.perf_counter()
    for _ in range(steps):
        loss = step(ids, labels)
    float(loss)
    dt = _time.perf_counter() - t0
    n_dev = len(jax.devices())
    return {
        "bert_mrpc_samples_per_sec_per_chip": round(batch * steps / dt / n_dev, 1),
        "bert_step_ms": round(dt / steps * 1e3, 2),
        "bert_compile_s": round(compile_s, 1),
    }


def _big_model_inference_workload(on_accel: bool) -> dict:
    """Reference benchmark form (benchmarks/big_model_inference/README.md):
    model load time + per-token generation latency, on the largest GPT that
    comfortably fits one chip (GPT-2-large, 774M) with a KV-cache decode."""
    import time as _time

    import jax

    import accelerate_tpu.nn as nn
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    import numpy as np

    nn.manual_seed(0)
    acc = Accelerator(mixed_precision="bf16")
    cfg = GPTConfig.large() if on_accel else GPTConfig.tiny()
    t0 = _time.perf_counter()
    model = GPTLMHeadModel(cfg)
    model = acc.prepare(model)
    model.eval()
    load_s = _time.perf_counter() - t0

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, 128 if on_accel else 16), dtype=np.int32)
    new = 64 if on_accel else 4
    t0 = _time.perf_counter()
    out = model.generate(prompt, max_new_tokens=new)
    jax.block_until_ready(out)
    _ = np.asarray(out)  # host sync through the transport
    compile_s = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    out = model.generate(prompt, max_new_tokens=new)
    _ = np.asarray(out)
    gen_s = _time.perf_counter() - t0
    return {
        "bigmodel_params_m": round(model.num_parameters / 1e6, 1),
        "bigmodel_load_s": round(load_s, 2),
        "bigmodel_generate_s_per_token": round(gen_s / new, 4),
        "bigmodel_generate_compile_s": round(compile_s, 1),
    }


def _llama_fsdp_workload(on_accel: bool) -> dict:
    """BASELINE.json config 4: FSDP-sharded Llama-family training.

    On one chip the fsdp axis is 1 (ZeRO needs peers to shard over), so the
    measured thing is the Llama block math (RMSNorm/RoPE/SwiGLU/GQA) at a
    7B-like width scaled to fit one v5e; the sharded path itself is proven
    on the 8-device mesh in tests/test_llama.py and __graft_entry__.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    # the singleton still carries the primary workload's dp-only config; a
    # conflicting ParallelismConfig re-init raises without a reset
    Accelerator._reset_state()
    nn.manual_seed(0)
    n_dev = len(jax.devices())
    fsdp = n_dev if n_dev > 1 else 1
    acc = Accelerator(
        parallelism_config=ParallelismConfig(fsdp_size=fsdp), mixed_precision="bf16"
    )
    if on_accel:
        # 7B layer ratios (head 128, inter/hidden ≈ 2.7, GQA 4:1) at a width
        # whose AdamW state fits one 16 GB chip
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=8, num_attention_heads=16, num_key_value_heads=4,
            max_position_embeddings=2048,
        )
        batch, seq, steps = 4, 1024, 20
    else:
        cfg = LlamaConfig.tiny()
        batch, seq, steps = 2, 32, 2
    model = LlamaForCausalLM(cfg)
    opt = optim.AdamW(model.parameters(), lr=1e-4)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    rng = np.random.default_rng(0)
    ids = batch_to_global_array(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (batch * max(1, n_dev), seq)), jnp.int32),
        mesh=acc.mesh,
    )
    t0 = _time.perf_counter()
    float(step(ids))
    compile_s = _time.perf_counter() - t0
    float(step(ids))
    t0 = _time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    float(loss)
    dt = _time.perf_counter() - t0
    tokens_per_sec = batch * max(1, n_dev) * seq * steps / dt / n_dev
    flops = tokens_per_sec * 6 * model.num_parameters
    return {
        "llama_params_m": round(model.num_parameters / 1e6, 1),
        "llama_train_tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "llama_mfu_pct": round(flops / TPU_PEAK_FLOPS * 100, 1) if on_accel else None,
        "llama_compile_s": round(compile_s, 1),
        "llama_fsdp_size": fsdp,
    }


def _timed_steps(step, batches: list, steps: int, warmup: int):
    """The one timing methodology every GPT-throughput row uses: compile on
    batch 0, warm across rotated batches, then time `steps` rotated calls.
    Returns (compile_s, dt, final_loss, recompile, arg_assembly_ms):
    ``recompile`` is ``{"count", "first_cause", "recompiled"}`` — from the
    telemetry forensics stream (accelerate_tpu.telemetry, cause strings
    naming what changed) when the accelerator runs with telemetry on, else
    derived from the capture-cache size (legacy detection, no cause);
    ``arg_assembly_ms`` is the mean host-side argument-assembly time per
    replay during the timed window (CapturedStep accounting)."""
    t0 = time.perf_counter()
    loss = step(batches[0])
    float(loss)
    compile_s = time.perf_counter() - t0
    for i in range(max(0, warmup - 1)):
        loss = step(batches[(i + 1) % len(batches)])
    float(loss)  # force full sync before timing
    n_cached = len(step._cache)
    tel = getattr(step, "_telemetry", None)
    events0 = tel.recompiles_total if tel is not None else 0
    asm_ms0 = getattr(step, "host_assembly_ms_total", 0.0)
    asm_n0 = getattr(step, "host_assembly_calls", 0)
    t0 = time.perf_counter()
    for i in range(steps):
        loss = step(batches[i % len(batches)])
    final_loss = float(loss)  # device sync: everything above has completed
    dt = time.perf_counter() - t0
    asm_calls = getattr(step, "host_assembly_calls", 0) - asm_n0
    asm_ms = (
        (getattr(step, "host_assembly_ms_total", 0.0) - asm_ms0) / asm_calls
        if asm_calls
        else None
    )
    if tel is not None:
        count = tel.recompiles_total - events0
        new_events = list(tel.recompile_events)[-count:] if count else []
        recompile = {
            "count": count,
            "first_cause": new_events[0].cause if new_events else None,
            "recompiled": count > 0,
        }
    else:
        recompiled = len(step._cache) != n_cached
        recompile = {
            "count": int(recompiled),
            "first_cause": None,
            "recompiled": recompiled,
        }
    return compile_s, dt, final_loss, recompile, asm_ms


def _fp8_ab_workload(on_accel: bool) -> dict:
    """fp8 matmul A/B on the flagship geometry.

    Same GPT config/batch/seq as the primary bf16 row, trained with
    ``mixed_precision="fp8"`` (utils/fp8.py HYBRID recipe). The ratio row is
    the deliverable: v5e/v4 MXUs have no fp8 datapath, so fp8 there pays
    quantize/dequant FLOPs for bandwidth savings only — if the ratio is < 1
    on this part, bf16 stays the default and the number documents why.
    Convergence parity vs bf16 is asserted in
    tests/test_precision.py::test_fp8_convergence_parity_vs_bf16 (reference
    benchmarks/fp8/torchao/non_distributed.py pattern).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator, TelemetryKwargs
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    Accelerator._reset_state()
    nn.manual_seed(0)
    # telemetry ON to match the primary bf16 row: both sides must pay the
    # same instrumentation (AOT dispatch, per-step records) or the ratio
    # compares methodologies instead of datapaths
    acc = Accelerator(
        mixed_precision="fp8", kwargs_handlers=[TelemetryKwargs(enabled=True)]
    )
    n_dev = len(jax.devices())
    cfg = GPTConfig.small() if on_accel else GPTConfig.tiny()
    batch, seq, steps = (BATCH * n_dev, SEQ, 20) if on_accel else (2, 128, 2)
    model = GPTLMHeadModel(cfg)
    opt = optim.AdamW(model.parameters(), lr=3e-4, weight_decay=0.1)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    rng = np.random.default_rng(0)
    batches = [
        batch_to_global_array(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
            mesh=acc.mesh,
        )
        for _ in range(4)
    ]
    # same methodology as the primary bf16 row (rotated batches, WARMUP,
    # recompile detection) so the ratio is apples-to-apples
    compile_s, dt, final_loss, recompile, _ = _timed_steps(
        step, batches, steps, WARMUP if on_accel else 1
    )
    tokens_per_sec = batch * seq * steps / dt / n_dev
    out = {
        "fp8_train_tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "fp8_compile_s": round(compile_s, 1),
        "fp8_final_loss": round(final_loss, 3),
        "fp8_recompiled_during_timing": recompile["recompiled"],
    }
    bf16 = _PRIMARY_RESULT.get("value")
    if bf16:
        out["fp8_vs_bf16_ratio"] = round(tokens_per_sec / bf16, 4)
    return out


def _compression_ab_block(on_accel: bool) -> dict:
    """Compression A/B rows for the primary workload JSON (docs/compression.md):
    the SAME GPT geometry trained under ``none`` / ``int8`` / ``fp8``
    dp-collective compression, reporting per-policy ``step_ms``,
    ``dp_collective_bytes`` (telemetry ``kind="collectives"`` accounting) and
    final loss — so the first on-TPU run captures
    the EQuARX-style bandwidth win without a new bench build.

    Skipped (with a reason row) when dp == 1: the policies quantize the
    ZeRO-1 dp collective pair, and a single chip has no dp traffic to
    compress.  ``BENCH_COMPRESSION=0`` disables the block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator, CompressionKwargs, TelemetryKwargs
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    n_dev = len(jax.devices())
    out: dict = {}
    if n_dev <= 1:
        out["compression_ab_skipped"] = "dp=1: no dp-axis collectives to compress"
        return out
    cfg = GPTConfig.small() if on_accel else GPTConfig.tiny()
    # batch is PER-CHIP × n_dev in both branches so the global batch always
    # divides the dp axis (this block only runs at dp > 1)
    batch, seq, steps = (BATCH * n_dev, SEQ, 20) if on_accel else (2 * n_dev, 128, 2)
    for policy in ("none", "int8", "fp8"):
        try:
            Accelerator._reset_state()
            nn.manual_seed(0)
            acc = Accelerator(
                mixed_precision="bf16",
                kwargs_handlers=[
                    TelemetryKwargs(enabled=True),
                    CompressionKwargs(policy=policy),
                ],
            )
            model = GPTLMHeadModel(cfg)
            opt = optim.AdamW(model.parameters(), lr=3e-4, weight_decay=0.1)
            model, opt = acc.prepare(model, opt)

            def step_fn(ids):
                opt.zero_grad()
                loss_out = model(ids, labels=ids)
                acc.backward(loss_out["loss"])
                opt.step()
                return loss_out["loss"]

            step = acc.compile_step(step_fn)
            rng = np.random.default_rng(0)
            batches = [
                batch_to_global_array(
                    jnp.asarray(
                        rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32
                    ),
                    mesh=acc.mesh,
                )
                for _ in range(4)
            ]
            compile_s, dt, final_loss, recompile, _ = _timed_steps(
                step, batches, steps, WARMUP if on_accel else 1
            )
            records = list(acc.telemetry.collective_records)
            bytes_total = (
                records[-1].stats.get("dp_collective_bytes") if records else None
            )
            out[f"compression_{policy}_step_ms"] = round(dt / steps * 1e3, 2)
            out[f"compression_{policy}_dp_collective_bytes"] = bytes_total
            out[f"compression_{policy}_final_loss"] = round(final_loss, 3)
            out[f"compression_{policy}_recompile_events"] = recompile["count"]
            out[f"compression_{policy}_compile_s"] = round(compile_s, 1)
        except Exception as exc:  # keep the other policies' rows
            _record_failure(out, f"compression_{policy}", exc)
    none_ms = out.get("compression_none_step_ms")
    int8_ms = out.get("compression_int8_step_ms")
    if none_ms and int8_ms:
        out["compression_int8_speedup"] = round(none_ms / int8_ms, 3)
    return out


def _flightrec_ab_block(on_accel: bool) -> dict:
    """Flight-recorder overhead A/B for the primary row (docs/telemetry.md):
    the SAME GPT geometry stepped with the always-on black-box flight
    recorder enabled (the default) vs force-disabled, reporting both
    ``step_ms`` rows and the relative overhead.  The recorder ships
    ON by default, so this row is the standing proof the ring's two
    lock-guarded dict writes per step stay inside the <=1%% budget.

    The recorder is pinned per CapturedStep at construction, so each arm
    flips ``flightrec.recorder().enabled`` BEFORE ``compile_step`` and a
    fresh Accelerator; the flag is restored afterwards regardless.
    ``BENCH_FLIGHTREC=0`` disables the block."""
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
    from accelerate_tpu.telemetry import flightrec

    out: dict = {}
    cfg = GPTConfig.small() if on_accel else GPTConfig.tiny()
    batch, seq, steps = (BATCH, SEQ, 20) if on_accel else (4, 128, 25)
    rec = flightrec.recorder()
    prior_enabled = rec.enabled
    try:
        Accelerator._reset_state()
        nn.manual_seed(0)
        acc = Accelerator(mixed_precision="bf16")
        model = GPTLMHeadModel(cfg)
        opt = optim.AdamW(model.parameters(), lr=3e-4, weight_decay=0.1)
        model, opt = acc.prepare(model, opt)

        def step_fn(ids):
            opt.zero_grad()
            loss_out = model(ids, labels=ids)
            acc.backward(loss_out["loss"])
            opt.step()
            return loss_out["loss"]

        # the recorder is pinned per CapturedStep at construction, so two
        # replays of the SAME program — one instrumented, one not — coexist
        # in one session and can be timed in alternating windows: interleaving
        # cancels the slow thermal/scheduler drift that dwarfs the ring's
        # two dict writes per step, and the min window per arm drops the noise
        rec.enabled = True
        step_on = acc.compile_step(step_fn)
        rec.enabled = False
        step_off = acc.compile_step(step_fn)
        rng = np.random.default_rng(0)
        batches = [
            batch_to_global_array(
                jnp.asarray(
                    rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32
                ),
                mesh=acc.mesh,
            )
            for _ in range(4)
        ]
        warmup = WARMUP if on_accel else 2
        best = {"on": None, "off": None}
        final_loss = None
        for _ in range(4):
            for arm, step in (("on", step_on), ("off", step_off)):
                _, dt, final_loss, _, _ = _timed_steps(
                    step, batches, steps, warmup
                )
                if best[arm] is None or dt < best[arm]:
                    best[arm] = dt
        for arm, dt in best.items():
            out[f"flightrec_{arm}_step_ms"] = round(dt / steps * 1e3, 3)
        out["flightrec_final_loss"] = round(final_loss, 3)
    finally:
        rec.enabled = prior_enabled
    on_ms = out.get("flightrec_on_step_ms")
    off_ms = out.get("flightrec_off_step_ms")
    if on_ms and off_ms:
        out["flightrec_overhead_pct"] = round((on_ms - off_ms) / off_ms * 100, 2)
    return out


def _aot_cache_block(on_accel: bool) -> dict:
    """Cold/warm AOT-executable-cache A/B for the primary row
    (docs/aot_cache.md): the SAME GPT step built twice against one cache
    dir.  The second build runs in a process-simulated fresh start —
    ``Accelerator._reset_state()`` plus ``jax.clear_caches()`` drop every
    in-memory jit/pjit entry, so the only thing that can skip trace+compile
    is the serialized executable on disk.  Reported: ``first_step_ms_cold``
    / ``first_step_ms_warm`` (the autoscaling cold-start the ROADMAP names),
    hit/miss counters, and the speedup ratio (acceptance: >= 5x on the CPU
    smoke geometry).  ``BENCH_AOT_CACHE=0`` disables the block."""
    import shutil
    import tempfile
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator, CompilationCacheKwargs, TelemetryKwargs
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    cache_dir = tempfile.mkdtemp(prefix="atpu_bench_aot_")
    n_dev = len(jax.devices())
    cfg = GPTConfig.small() if on_accel else GPTConfig.tiny()
    batch, seq = (BATCH * n_dev, SEQ) if on_accel else (2, 128)

    def build_once() -> tuple[float, float, int, int]:
        Accelerator._reset_state()
        jax.clear_caches()
        nn.manual_seed(0)
        acc = Accelerator(
            mixed_precision="bf16" if on_accel else "no",
            kwargs_handlers=[
                TelemetryKwargs(enabled=True),
                CompilationCacheKwargs(cache_dir=cache_dir),
            ],
        )
        model = GPTLMHeadModel(cfg)
        opt = optim.AdamW(model.parameters(), lr=3e-4, weight_decay=0.1)
        model, opt = acc.prepare(model, opt)

        def step_fn(ids):
            opt.zero_grad()
            out = model(ids, labels=ids)
            acc.backward(out["loss"])
            opt.step()
            return out["loss"]

        step = acc.compile_step(step_fn)
        ids = batch_to_global_array(
            jnp.asarray(
                np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
                jnp.int32,
            ),
            mesh=acc.mesh,
        )
        t0 = _time.perf_counter()
        loss = float(step(ids))
        first_ms = (_time.perf_counter() - t0) * 1e3
        return first_ms, loss, acc.aot_cache.hits, acc.aot_cache.misses

    try:
        cold_ms, cold_loss, _, cold_misses = build_once()
        warm_ms, warm_loss, warm_hits, warm_misses = build_once()
        return {
            "first_step_ms_cold": round(cold_ms, 1),
            "first_step_ms_warm": round(warm_ms, 1),
            "aot_cache_hits": warm_hits,
            "aot_cache_misses": cold_misses + warm_misses,
            "aot_cache_speedup": round(cold_ms / warm_ms, 2) if warm_ms else None,
            "aot_cache_loss_bitwise_equal": cold_loss == warm_loss,
        }
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


def _elastic_block(on_accel: bool) -> dict:
    """Elastic-resize rehearsal timing for the primary row
    (docs/elastic.md): tiny GPT at the full dp extent, ``fleet.resize()``
    to dp/2, then one resumed step.  Reported: the drain/remesh+restore
    split (``elastic_drain_ms`` / ``elastic_resize_ms``), the AOT entries
    prewarmed for the surviving topology, the post-resize first-step wall
    clock (the recovery-time number an autoscaler plans around) and the
    resumed-step relative loss error vs continuing at full dp.
    After the resumed step the lost half "returns" and ``fleet.grow()``
    re-meshes back to full dp (docs/elastic.md §grow) — the grow-side
    recovery row: ``elastic_grow_ms`` (drain + rendezvous + remesh +
    reshard restore) and ``elastic_post_grow_step_ms``.  No cold/warm
    split for the grow direction: a grow-back is warm BY CONSTRUCTION —
    the run compiled and stored its own full-dp program before the loss,
    so the prewarm always serves it (the split would compare the store
    against itself).
    Run TWICE against one AOT store: the cold pass compiles the dp/2
    program at resize time, the warm pass recovers off the prewarmed
    serialized executable — the cold/warm post-SHRINK split is the
    with/without-store recovery story.
    ``BENCH_ELASTIC=0`` disables the block."""
    import tempfile
    import time as _time

    import jax
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import (
        Accelerator,
        CompilationCacheKwargs,
        FleetKwargs,
        TelemetryKwargs,
    )
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"elastic_skipped": f"needs >= 2 devices, have {n_dev}"}
    tmp = tempfile.mkdtemp(prefix="atpu_bench_elastic_")
    cache_dir = os.path.join(tmp, "aot")
    cfg = GPTConfig.small() if on_accel else GPTConfig.tiny()
    batch, seq = (BATCH * n_dev, SEQ) if on_accel else (4, 128)

    def build(fleet: bool):
        Accelerator._reset_state()
        jax.clear_caches()
        nn.manual_seed(0)
        handlers = [TelemetryKwargs(enabled=True)]
        if fleet:
            handlers += [
                FleetKwargs(enabled=True),
                CompilationCacheKwargs(cache_dir=cache_dir),
            ]
        acc = Accelerator(
            mixed_precision="bf16" if on_accel else "no",
            kwargs_handlers=handlers,
        )
        model = GPTLMHeadModel(cfg)
        opt = optim.AdamW(model.parameters(), lr=3e-4, weight_decay=0.1)
        model, opt = acc.prepare(model, opt)

        def step_fn(ids):
            opt.zero_grad()
            out = model(ids, labels=ids)
            acc.backward(out["loss"])
            opt.step()
            return out["loss"]

        rng = np.random.default_rng(0)
        raw = [
            rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
            for _ in range(4)
        ]
        return acc, acc.compile_step(step_fn), raw

    def rehearse():
        acc, step, raw = build(fleet=True)
        dp = dict(acc.mesh.shape)["dp"]
        for b in raw[:2]:
            float(step(batch_to_global_array(b, mesh=acc.mesh)))
        t0 = _time.perf_counter()
        ckpt = acc.fleet.drain(acc, os.path.join(tmp, "drain"))
        t1 = _time.perf_counter()
        info = acc.fleet.resize(acc, target_dp=dp // 2, checkpoint=ckpt)
        t2 = _time.perf_counter()
        resumed = float(step(batch_to_global_array(raw[2], mesh=acc.mesh)))
        t3 = _time.perf_counter()
        # grow-side recovery: the lost half returns, the fleet re-meshes
        # back to full dp (drain + rendezvous + remesh + reshard restore)
        ginfo = acc.fleet.grow(
            acc, target_dp=dp, output_dir=os.path.join(tmp, "drain_grow")
        )
        t4 = _time.perf_counter()
        regrown = float(step(batch_to_global_array(raw[3], mesh=acc.mesh)))
        t5 = _time.perf_counter()
        return (
            dp, info, resumed, (t1 - t0, t2 - t1, t3 - t2),
            ginfo, regrown, (t4 - t3, t5 - t4),
        )

    try:
        # reference: full-dp run over the same batches
        acc, step, raw = build(fleet=False)
        ref = [
            float(step(batch_to_global_array(b, mesh=acc.mesh))) for b in raw
        ]
        dp, _, _, cold, _, _, _ = rehearse()
        _, info, resumed, warm, ginfo, regrown, warm_grow = rehearse()
        return {
            "elastic_dp": f"{dp}->{dp // 2}",
            "elastic_drain_ms": round(warm[0] * 1e3, 1),
            "elastic_resize_ms": round(warm[1] * 1e3, 1),
            "elastic_prewarm_entries": info["aot_prewarmed"],
            "elastic_post_resize_step_ms_cold": round(cold[2] * 1e3, 1),
            "elastic_post_resize_step_ms_warm": round(warm[2] * 1e3, 1),
            "elastic_resume_loss_rel_err": (
                round(abs(resumed - ref[2]) / max(abs(ref[2]), 1e-9), 8)
            ),
            "elastic_grow_dp": f"{dp // 2}->{dp}",
            "elastic_grow_ms": round(warm_grow[0] * 1e3, 1),
            "elastic_grow_prewarm_entries": ginfo["aot_prewarmed"],
            # warm-by-construction: the run stored its own full-dp program
            # before the loss, so there is no honest "cold" grow-back arm
            "elastic_post_grow_step_ms": round(warm_grow[1] * 1e3, 1),
            "elastic_regrow_loss_rel_err": (
                round(abs(regrown - ref[3]) / max(abs(ref[3]), 1e-9), 8)
            ),
        }
    finally:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def _serving_block(on_accel: bool) -> dict:
    """Serving rows for the primary JSON (docs/serving.md): the continuous-
    batching decode service on the flagship GPT geometry under a synthetic
    Poisson request trace — p50/p99 TTFT, p50/p99 per-token latency,
    aggregate generated tokens/s, mean batch occupancy,
    ``serving_recompile_events`` (the zero-recompile steady-state contract,
    counted by the engine's CompileWatcher forensics; must be 0 after
    warmup) and ``serving_host_syncs_per_token`` (dispatch-overhead gauge).

    Plus the device-resident multi-token A/B (ISSUE 14): the SAME trace
    re-run with ``decode_steps=$BENCH_DECODE_STEPS`` (default 8 — 0/1
    disables the leg), reported as ``serving_multistep_*`` rows with a
    tokens/s speedup against the per-token leg.  ``BENCH_SERVING=0``
    disables the whole block."""
    import time as _time

    import numpy as np

    import accelerate_tpu.nn as nn
    from accelerate_tpu import Accelerator, DecodeService, ServingConfig
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
    from accelerate_tpu.serving import bucket_length

    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(mixed_precision="bf16" if on_accel else "no")
    cfg = GPTConfig.small() if on_accel else GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    model = acc.prepare(model)
    model.eval()

    if on_accel:
        n_requests, max_new, rate_per_s = 32, 64, 8.0
        geometry = dict(max_slots=8, block_size=32, prompt_bucket=64)
        prompt_lens = (24, 57, 128, 200, 96, 33, 160, 80)
    else:
        n_requests, max_new, rate_per_s = 8, 8, 200.0
        geometry = dict(max_slots=4, block_size=16, prompt_bucket=16)
        prompt_lens = (3, 9, 17, 30)

    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_per_s, size=n_requests))
    prompts = [
        rng.integers(0, cfg.vocab_size, (prompt_lens[i % len(prompt_lens)],), dtype=np.int32)
        for i in range(n_requests)
    ]

    def run_trace(decode_steps: int, trace_max_new: int,
                  journal_dir=None) -> dict:
        service = DecodeService(
            model,
            ServingConfig(decode_steps=decode_steps, journal_dir=journal_dir,
                          **geometry),
            telemetry=acc.telemetry,
        )
        # warmup: compile the decode program + every prefill bucket the
        # trace uses BEFORE the clock starts, so the latency percentiles
        # measure the steady state and the recompile counter's warmup set
        # is primed
        buckets = sorted(
            {bucket_length(len(p), geometry["prompt_bucket"]) for p in prompts}
        )
        warm_rids = {
            service.submit(np.ones(blen, np.int32),
                           max_new_tokens=decode_steps + 1)
            for blen in buckets
        }
        service.run()
        warm_compiles = service.watcher.compiles_total
        # occupancy/sync statistics restart at the measured trace (the
        # warmup requests ran near-solo and would dilute the means)
        service.stats.update(
            steps=0, occupancy_sum=0.0, decode_syncs=0, decode_tokens=0
        )

        t0 = _time.perf_counter()
        submitted = 0
        while submitted < n_requests or service.has_work:
            now = _time.perf_counter() - t0
            while submitted < n_requests and arrivals[submitted] <= now:
                # backdate the TTFT clock to the Poisson ARRIVAL: several
                # arrivals can come due during one decode step, and
                # starting their clocks at submit would exclude exactly
                # the queueing tail the p99 row exposes (coordinated
                # omission)
                service.submit(
                    prompts[submitted], max_new_tokens=trace_max_new,
                    arrival_t=t0 + arrivals[submitted],
                )
                submitted += 1
            if service.has_work:
                service.step()
            elif submitted < n_requests:
                _time.sleep(min(0.001, arrivals[submitted] - now))
        dt = _time.perf_counter() - t0

        reqs = [r for r in service.results.values() if r.rid not in warm_rids]
        ttft = sorted(r.ttft_ms for r in reqs)
        tpot = sorted(r.tpot_ms for r in reqs if r.tpot_ms is not None)

        def pct(vals, q):
            return round(vals[min(len(vals) - 1, int(q * len(vals)))], 2) if vals else None

        total_tokens = sum(len(r.tokens) for r in reqs)
        return {
            "requests": len(reqs),
            "ttft_p50_ms": pct(ttft, 0.50),
            "ttft_p99_ms": pct(ttft, 0.99),
            "tpot_p50_ms": pct(tpot, 0.50),
            "tpot_p99_ms": pct(tpot, 0.99),
            "tokens_per_sec": round(total_tokens / dt, 1),
            "mean_occupancy": round(service.mean_batch_occupancy, 3),
            "recompile_events": service.recompile_events,
            "warmup_compiles": warm_compiles,
            "host_syncs_per_token": round(service.host_syncs_per_token, 4),
        }

    base = run_trace(1, max_new)
    out = {f"serving_{k}": v for k, v in base.items()}
    out["serving_max_slots"] = geometry["max_slots"]
    out["serving_block_size"] = geometry["block_size"]

    # the device-resident A/B leg: same trace, n-token captured blocks.
    # Budgets stretch to cover whole blocks (n*3+1) so the syncs-per-token
    # ratio measures the loop, not truncation by tiny budgets — the n=1
    # denominator for the speedup is re-run at the SAME budgets
    from accelerate_tpu.utils.dataclasses import env_int

    n = env_int("BENCH_DECODE_STEPS", 8)
    if n > 1:
        ab_max_new = max(max_new, 3 * n + 1)
        ab_base = base if ab_max_new == max_new else run_trace(1, ab_max_new)
        multi = run_trace(n, ab_max_new)
        out["serving_multistep_decode_steps"] = n
        for key in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                    "tpot_p99_ms", "tokens_per_sec", "mean_occupancy",
                    "recompile_events", "host_syncs_per_token"):
            out[f"serving_multistep_{key}"] = multi[key]
        if ab_base is not base:
            out["serving_multistep_base_tokens_per_sec"] = ab_base["tokens_per_sec"]
            out["serving_multistep_base_host_syncs_per_token"] = (
                ab_base["host_syncs_per_token"]
            )
        if ab_base["tokens_per_sec"]:
            out["serving_multistep_speedup"] = round(
                multi["tokens_per_sec"] / ab_base["tokens_per_sec"], 2
            )

    # fault-tolerance rows (docs/serving.md §fault tolerance), gated off by
    # default (BENCH_SERVING_CHAOS=1 enables): the journal-on steady-state
    # TPOT overhead (<5% is the acceptance bound), and a preemption drill —
    # a journaled replica abandoned mid-flight, a fresh replica resumed
    # from its journal.  serving_requests_lost MUST be 0 and the recovery
    # re-prefills must not compile (warm in-trace programs).
    import os as _os

    if _os.environ.get("BENCH_SERVING_CHAOS", "0").lower() not in (
        "0", "", "false"
    ):
        import shutil as _shutil
        import tempfile as _tempfile

        scratch = _tempfile.mkdtemp(prefix="bench-serving-chaos-")
        try:
            journaled = run_trace(
                1, max_new, journal_dir=_os.path.join(scratch, "steady")
            )
            out["serving_journal_tpot_p50_ms"] = journaled["tpot_p50_ms"]
            if base["tpot_p50_ms"]:
                out["serving_journal_tpot_overhead_pct"] = round(
                    (journaled["tpot_p50_ms"] - base["tpot_p50_ms"])
                    / base["tpot_p50_ms"] * 100.0, 2
                )

            # the preemption drill: all requests in flight, replica A dies
            # (no drain — the raw-WAL worst case) after a few steps
            drill_dir = _os.path.join(scratch, "drill")
            svc_a = DecodeService(
                model, ServingConfig(journal_dir=drill_dir, **geometry),
                telemetry=acc.telemetry,
            )
            for p in prompts:
                svc_a.submit(p, max_new_tokens=max_new)
            for _ in range(3):
                svc_a.step()
            done_a = sum(
                1 for r in svc_a.results.values() if r.state == "done"
            )
            del svc_a
            svc_b = DecodeService(
                model, ServingConfig(journal_dir=drill_dir, **geometry),
                telemetry=acc.telemetry,
            )
            t0 = _time.perf_counter()
            svc_b.resume_from_journal()
            while svc_b.metrics()["queue_depth"] > 0:
                svc_b.step()
            # recovery_ms: journal replay + re-admission (every resumed
            # request re-prefilled or slotted) on the fresh replica
            out["serving_recovery_ms"] = round(
                (_time.perf_counter() - t0) * 1e3, 2
            )
            svc_b.run()
            done_b = [
                r for r in svc_b.results.values() if r.state == "done"
            ]
            out["serving_requests_lost"] = (
                n_requests - done_a - len(done_b)
            )
            out["serving_recovery_recompile_events"] = svc_b.recompile_events
            recovered_tpot = sorted(
                r.tpot_ms for r in done_b if r.tpot_ms is not None
            )
            rec_p50 = (
                round(recovered_tpot[len(recovered_tpot) // 2], 2)
                if recovered_tpot else None
            )
            out["serving_recovered_tpot_p50_ms"] = rec_p50
            if rec_p50 is not None and base["tpot_p50_ms"]:
                # recovered-vs-uninterrupted per-token latency delta: the
                # re-prefill rebuilds KV off the clock path, so recovered
                # decode should run at steady-state speed
                out["serving_recovered_tpot_delta_pct"] = round(
                    (rec_p50 - base["tpot_p50_ms"])
                    / base["tpot_p50_ms"] * 100.0, 2
                )
        finally:
            _shutil.rmtree(scratch, ignore_errors=True)
    return out


def _kernels_ab_block(on_accel: bool) -> dict:
    """Per-kernel on/off A/B rows for the primary JSON (docs/kernels.md):
    the SAME GPT geometry trained with each training kernel armed vs off
    (``kernel_<name>_step_ms_{off,on}`` + ``kernel_<name>_speedup`` + dp
    bytes).  On the CPU interpreter the kernels exist for correctness,
    not speed — the A/B is the harness the first on-TPU window fills with
    the real fusion win.  ``BENCH_KERNELS=0`` disables the block; rows are
    fail-soft per kernel like the compression A/B."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import (
        Accelerator,
        CompressionKwargs,
        KernelKwargs,
        TelemetryKwargs,
    )
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    n_dev = len(jax.devices())
    out: dict = {"kernels_interpret": not on_accel}
    cfg = GPTConfig.small() if on_accel else GPTConfig.tiny()
    batch, seq, steps = (BATCH * n_dev, SEQ, 20) if on_accel else (2 * n_dev, 128, 3)

    def train_ms(kernels: str, policy: str):
        Accelerator._reset_state()
        nn.manual_seed(0)
        acc = Accelerator(
            mixed_precision="bf16",
            kwargs_handlers=[
                TelemetryKwargs(enabled=True),
                CompressionKwargs(policy=policy),
                KernelKwargs(kernels=kernels),
            ],
        )
        model = GPTLMHeadModel(cfg)
        opt = optim.AdamW(model.parameters(), lr=3e-4, weight_decay=0.1)
        model, opt = acc.prepare(model, opt)

        def step_fn(ids):
            opt.zero_grad()
            loss_out = model(ids, labels=ids)
            acc.backward(loss_out["loss"])
            opt.step()
            return loss_out["loss"]

        step = acc.compile_step(step_fn)
        rng = np.random.default_rng(0)
        batches = [
            batch_to_global_array(
                jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
                mesh=acc.mesh,
            )
            for _ in range(4)
        ]
        _, dt, final_loss, recompile, _ = _timed_steps(
            step, batches, steps, WARMUP if on_accel else 1
        )
        records = list(acc.telemetry.collective_records)
        bytes_total = records[-1].stats.get("dp_collective_bytes") if records else None
        return dt / steps * 1e3, final_loss, recompile["count"], bytes_total

    from accelerate_tpu.native.kernels import TPU_REFUSED

    refused = TPU_REFUSED if on_accel else {}
    for name, why in refused.items():
        out[f"kernel_{name}_skipped"] = f"refused by the TPU compiler: {why}"
    if n_dev > 1:
        for name, policy in (("collective_matmul", "none"), ("quantized_rs", "int8")):
            if name in refused:
                continue
            try:
                off_ms, off_loss, _, off_bytes = train_ms("none", policy)
                on_ms, on_loss, on_rec, on_bytes = train_ms(name, policy)
                out[f"kernel_{name}_step_ms_off"] = round(off_ms, 2)
                out[f"kernel_{name}_step_ms_on"] = round(on_ms, 2)
                out[f"kernel_{name}_speedup"] = round(off_ms / on_ms, 3)
                # the armed run's own figure, even if None — substituting
                # the off-arm's bytes would mislabel the A/B row
                out[f"kernel_{name}_dp_bytes"] = on_bytes
                out[f"kernel_{name}_recompile_events"] = on_rec
                out[f"kernel_{name}_loss_delta"] = round(abs(on_loss - off_loss), 6)
            except Exception as exc:  # keep the other kernels' rows
                _record_failure(out, f"kernel_{name}", exc)
    else:
        out["kernel_training_skipped"] = "dp=1: no dp collective pair to fuse"

    return out


def _pipeline_block(on_accel: bool) -> dict:
    """Fused vs interleaved 1F1B A/B on the pp=2 × dp geometry
    (docs/parallel_plan.md): step_ms for each schedule, the analytic
    bubble-tick/bubble-fraction profile, and ``pipeline_interleave_speedup``
    (fused/interleaved step_ms).  On the lockstep CPU rehearsal the masked
    ramp slots keep wall clock near parity — the analytic bubble columns
    carry the MPMD gain the per-stage AOT programs realize on hardware;
    the first on-TPU window fills the measured speedup.
    ``BENCH_PIPELINE=0`` disables the block; rows are fail-soft."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator, ParallelismConfig, TelemetryKwargs
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, PipelinedGPTLMHeadModel
    from accelerate_tpu.parallel.pipeline import bubble_fraction, bubble_ticks
    from accelerate_tpu.utils.dataclasses import PipelineParallelPlugin

    n_dev = len(jax.devices())
    out: dict = {}
    if n_dev < 2 or n_dev % 2:
        out["pipeline_skipped"] = f"needs an even device count >= 2, have {n_dev}"
        return out
    S, V, M = 2, 2, 8
    import dataclasses as _dc

    # layer count must divide S·V = 4: small() is 12, tiny bumps 2 → 4
    cfg = (
        GPTConfig.small() if on_accel else _dc.replace(GPTConfig.tiny(), n_layer=4)
    )
    batch, seq, steps = (BATCH * n_dev, SEQ, 20) if on_accel else (8 * n_dev, 64, 3)

    def train_ms(schedule: str, virtual: int, layout: str = None):
        Accelerator._reset_state()
        nn.manual_seed(0)
        acc = Accelerator(
            mixed_precision="bf16" if on_accel else "no",
            parallelism_config=ParallelismConfig(pp_size=S),
            pp_plugin=PipelineParallelPlugin(
                pp_size=S, num_microbatches=M, schedule=schedule,
                virtual_stages=virtual, layout=layout,
            ),
            kwargs_handlers=[TelemetryKwargs(enabled=True)],
        )
        model = PipelinedGPTLMHeadModel(cfg, num_microbatches=M)
        opt = optim.AdamW(model.parameters(), lr=3e-4)
        model, opt = acc.prepare(model, opt)
        # analytic permutation traffic of THIS run's resolved layout
        # (StagePlan.permutation_bytes: gather moves ~(1−1/V)·stack twice
        # per step, committed/plain move zero — the layout A/B row)
        from accelerate_tpu.models.gpt import _StackedBlocks

        stacked = {n: getattr(model.blocks, n).data for n in _StackedBlocks._ORDER}
        perm_bytes = (
            acc.plan.stage.permutation_bytes(stacked)
            if acc.plan.stage is not None else 0
        )

        def step_fn(ids):
            opt.zero_grad()
            loss_out = model(ids, labels=ids)
            acc.backward(loss_out["loss"])
            opt.step()
            return loss_out["loss"]

        step = acc.compile_step(step_fn)
        rng = np.random.default_rng(0)
        batches = [
            batch_to_global_array(
                jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
                mesh=acc.mesh,
            )
            for _ in range(4)
        ]
        _, dt, final_loss, recompile, _ = _timed_steps(
            step, batches, steps, WARMUP if on_accel else 1
        )
        return dt / steps * 1e3, final_loss, recompile["count"], perm_bytes

    try:
        fused_ms, fused_loss, fused_rec, _ = train_ms("1f1b", 1)
        inter_ms, inter_loss, inter_rec, inter_pb = train_ms("interleaved", V)
        out["pipeline_fused_step_ms"] = round(fused_ms, 2)
        out["pipeline_interleaved_step_ms"] = round(inter_ms, 2)
        out["pipeline_interleave_speedup"] = round(fused_ms / max(inter_ms, 1e-9), 3)
        out["pipeline_loss_delta"] = round(abs(fused_loss - inter_loss), 6)
        out["pipeline_recompiles"] = fused_rec + inter_rec
        out["pipeline_bubble_ticks_fused"] = bubble_ticks(M, S, 1, granularity=V)
        out["pipeline_bubble_ticks_interleaved"] = bubble_ticks(M, S, V, granularity=V)
        out["pipeline_bubble_fraction_fused"] = bubble_fraction(M, S, 1)
        out["pipeline_bubble_fraction_interleaved"] = bubble_fraction(M, S, V)
        out["pipeline_geometry"] = {"pp": S, "virtual": V, "microbatches": M,
                                    "dp": n_dev // S}
        # layout A/B (ISSUE 17): committed (prepare-time permutation, the
        # default above) vs the legacy in-program gather — same math
        # (expected bitwise), different steady-state program
        gat_ms, gat_loss, gat_rec, gat_pb = train_ms(
            "interleaved", V, layout="gather"
        )
        out["pipeline_layout_step_ms"] = {
            "committed": round(inter_ms, 2), "gather": round(gat_ms, 2),
        }
        out["pipeline_layout_speedup"] = round(gat_ms / max(inter_ms, 1e-9), 3)
        out["pipeline_permutation_bytes"] = {
            "committed": inter_pb, "gather": gat_pb,
        }
        out["pipeline_layout_loss_delta"] = round(abs(inter_loss - gat_loss), 9)
        out["pipeline_recompiles"] += gat_rec
    except Exception as exc:  # noqa: BLE001 — keep the rows measured so far
        _record_failure(out, "pipeline", exc)
    return out


def _opt_inference_workload(on_accel: bool) -> dict:
    """BASELINE.json config 5: OPT device_map='auto'-style sharded inference
    (reference benchmarks/big_model_inference/README.md:31-37 form: load
    time + per-token decode latency)."""
    import time as _time

    import jax
    import numpy as np

    import accelerate_tpu.nn as nn
    from accelerate_tpu import Accelerator
    from accelerate_tpu.big_modeling import shard_for_inference
    from accelerate_tpu.models import OPTConfig, OPTForCausalLM

    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(mixed_precision="bf16")
    t0 = _time.perf_counter()
    cfg = OPTConfig.opt_1_3b() if on_accel else OPTConfig.tiny()
    model = shard_for_inference(OPTForCausalLM(cfg), mesh=acc.mesh)
    model.eval()
    load_s = _time.perf_counter() - t0
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, 128 if on_accel else 16), dtype=np.int32)
    new = 64 if on_accel else 4
    t0 = _time.perf_counter()
    out = model.generate(prompt, max_new_tokens=new)
    _ = np.asarray(out)
    compile_s = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    out = model.generate(prompt, max_new_tokens=new)
    _ = np.asarray(out)
    gen_s = _time.perf_counter() - t0
    # int8-weight decode A/B: decode is memory-bound, so 1-byte weight
    # streaming should cut per-token latency (bnb int8 benchmark analog)
    _ = np.asarray(model.generate(prompt, max_new_tokens=new, quantize_weights=8))
    t0 = _time.perf_counter()
    _ = np.asarray(model.generate(prompt, max_new_tokens=new, quantize_weights=8))
    gen8_s = _time.perf_counter() - t0
    return {
        "opt_params_m": round(model.num_parameters / 1e6, 1),
        "opt_load_s": round(load_s, 2),
        "opt_generate_s_per_token": round(gen_s / new, 4),
        "opt_generate_int8_s_per_token": round(gen8_s / new, 4),
        "opt_generate_compile_s": round(compile_s, 1),
    }


def _long_context_workload(on_accel: bool) -> dict:
    """Long-context training row: GPT-2-small geometry at seq 4096 — the
    flash kernels' O(S) memory is what makes this fit where materialised
    attention would not (16 GB HBM, 4096² fp32 scores alone are 64 MB per
    head·batch before fusion)."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(mixed_precision="bf16")
    if on_accel:
        cfg = GPTConfig(n_positions=4096)  # small geometry, 4× context
        batch, seq, steps = 3, 4096, 12
    else:
        cfg = GPTConfig(
            vocab_size=1024, n_positions=512, n_embd=128, n_layer=2, n_head=4
        )
        batch, seq, steps = 1, 256, 2
    model = GPTLMHeadModel(cfg)
    opt = optim.AdamW(model.parameters(), lr=3e-4)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    n_dev = len(jax.devices())
    ids = batch_to_global_array(
        jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (batch * n_dev, seq)),
            jnp.int32,
        ),
        mesh=acc.mesh,
    )
    t0 = _time.perf_counter()
    float(step(ids))
    compile_s = _time.perf_counter() - t0
    float(step(ids))
    t0 = _time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    float(loss)
    dt = _time.perf_counter() - t0
    # batch here is PER-CHIP (unlike main(), whose batch is global), so the
    # per-chip rate needs no device-count correction
    tokens_per_sec = batch * seq * steps / dt
    flops = tokens_per_sec * model.num_flops_per_token
    return {
        "longctx_seq": seq,
        "longctx_tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "longctx_mfu_pct": round(flops / TPU_PEAK_FLOPS * 100, 1) if on_accel else None,
        "longctx_compile_s": round(compile_s, 1),
    }


def _sliding_window_workload(on_accel: bool) -> dict:
    """Sliding-window long-context row: Llama geometry, same model full-causal
    vs windowed — the narrowed flash k-grid visits only in-band tiles, so the
    windowed step should beat full causal at long seq (ops/flash_attention.py)."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_accel:
        base = dict(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=8,
            max_position_embeddings=8192,
        )
        batch, seq, steps, window = 1, 8192, 8, 1024
    else:
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
            max_position_embeddings=256,
        )
        batch, seq, steps, window = 1, 256, 2, 64

    def measure(sliding_window: int) -> float:
        Accelerator._reset_state()
        nn.manual_seed(0)
        acc = Accelerator(mixed_precision="bf16")
        model = LlamaForCausalLM(LlamaConfig(**base, sliding_window=sliding_window))
        opt = optim.AdamW(model.parameters(), lr=1e-4)
        model, opt = acc.prepare(model, opt)

        def step_fn(ids):
            opt.zero_grad()
            out = model(ids, labels=ids)
            acc.backward(out["loss"])
            opt.step()
            return out["loss"]

        step = acc.compile_step(step_fn)
        n_dev = len(jax.devices())
        ids = batch_to_global_array(
            jnp.asarray(
                np.random.default_rng(0).integers(0, base["vocab_size"], (batch * n_dev, seq)),
                jnp.int32,
            ),
            mesh=acc.mesh,
        )
        t0 = _time.perf_counter()
        float(step(ids))  # compile
        compile_s = _time.perf_counter() - t0
        float(step(ids))  # warm
        t0 = _time.perf_counter()
        for _ in range(steps):
            loss = step(ids)
        float(loss)
        return batch * seq * steps / (_time.perf_counter() - t0), compile_s

    full, full_compile_s = measure(0)
    windowed, win_compile_s = measure(window)
    return {
        "window_seq": seq,
        "window_size": window,
        "window_full_tokens_per_sec": round(full, 1),
        "window_banded_tokens_per_sec": round(windowed, 1),
        "window_speedup": round(windowed / full, 3),
        "window_compile_s": round(full_compile_s + win_compile_s, 1),
    }


def main(argv=None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--cpu", action="store_true",
        help="rehearse on the CPU backend at tiny sizes; rows are named for "
        "what they are (no per_chip metric, no MFU)",
    )
    args = parser.parse_args(argv)
    if args.cpu:
        # set before jax is imported: the environment alone selects the backend
        os.environ["JAX_PLATFORMS"] = "cpu"
    _arm_deadline()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator, enable_compilation_cache
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
    from accelerate_tpu.utils.memory import opt_state_bytes_per_replica

    cache_dir = enable_compilation_cache()
    platform = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind
    on_accel = platform == "tpu"
    if not on_accel and not (args.cpu and platform == "cpu"):
        # no probe, no fallback chain: a measurement that did not land on
        # the chip is not a measurement
        raise SystemExit(
            f"bench: JAX came up on {platform!r} ({device_kind}), not on a TPU; "
            "run on the chip, or pass --cpu for a CPU rehearsal"
        )
    global TPU_PEAK_FLOPS, _ON_ACCEL
    TPU_PEAK_FLOPS = peak_flops(device_kind) if on_accel else None
    _ON_ACCEL = on_accel

    nn.manual_seed(0)
    # telemetry ON for the primary workload: the forensics stream turns the
    # old recompiled-during-timing bool into counted, attributed events, and
    # the timeline gives the trace/compile split for the first build
    # (docs/telemetry.md; the AOT capture path is loss-bitwise-identical to
    # the plain jit path, asserted in tests/test_telemetry.py)
    from accelerate_tpu import TelemetryKwargs

    # sampled device-time attribution (docs/telemetry.md): BENCH_PROFILE_N
    # (or the library-wide ACCELERATE_TELEMETRY_PROFILE_N) turns on xprof
    # sampling at that cadence — the sampled steps block, so the timed
    # window keeps its async pipeline on every other call and the JSON
    # gains the EQuARX-style device-side split alongside the wire bytes
    profile_n = int(
        os.environ.get(
            "BENCH_PROFILE_N",
            os.environ.get("ACCELERATE_TELEMETRY_PROFILE_N", "0") or 0,
        )
        or 0
    )
    acc = Accelerator(
        mixed_precision="bf16",
        kwargs_handlers=[TelemetryKwargs(enabled=True, profile_every_n=profile_n)],
    )
    cfg = GPTConfig.small() if on_accel else GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    opt = optim.AdamW(model.parameters(), lr=3e-4, weight_decay=0.1)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    rng = np.random.default_rng(0)

    batch, seq, steps, warmup = BATCH * len(jax.devices()), SEQ, STEPS, WARMUP
    if not on_accel:
        # --cpu rehearsal: tiny model + geometry, the same code paths
        batch, seq, steps, warmup = 2, 128, 3, 1

    def make_batch(i):
        ids = rng.integers(0, cfg.vocab_size, size=(batch, seq), dtype=np.int32)
        return batch_to_global_array(jnp.asarray(ids), mesh=acc.mesh)

    batches = [make_batch(i) for i in range(4)]
    compile_s, dt, final_loss, recompile, arg_assembly_ms = _timed_steps(
        step, batches, steps, warmup
    )
    # trace/compile split of the first build, from the telemetry timeline
    first_build = acc.telemetry.timeline.first_build()

    n_devices = len(jax.devices())
    # the Accelerator dp-shards the batch over every visible chip: divide the
    # aggregate throughput down so the per-chip metric/MFU stay honest on
    # multi-chip hosts
    tokens_per_sec = batch * seq * steps / dt / n_devices
    n_params = model.num_parameters
    flops_per_token = 6 * n_params
    model_flops = tokens_per_sec * flops_per_token
    result = {
        "metric": "gpt2_small_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / A100_BASELINE_TOKENS_PER_SEC, 4),
        "platform": platform,
        "device_kind": device_kind,
        "n_devices": n_devices,
        "peak_bf16_flops": TPU_PEAK_FLOPS,
        "compilation_cache_dir": cache_dir,
        "params_m": round(n_params / 1e6, 1),
        "batch": batch,
        "seq": seq,
        "steps": steps,
        "step_ms": round(dt / steps * 1e3, 2),
        "first_step_s": round(compile_s, 1),
        "model_tflops": round(model_flops / 1e12, 2),
        "mfu_pct": round(model_flops / TPU_PEAK_FLOPS * 100, 1) if on_accel else None,
        "final_loss": round(final_loss, 3),
        # recompile forensics (telemetry pillar 2): count + first attributed
        # cause during the timed window; the bool is derived from the count
        "recompile_events": recompile["count"],
        "recompile_first_cause": recompile["first_cause"],
        "recompiled_during_timing": recompile["recompiled"],
        "trace_ms": round(first_build.trace_ms, 1) if first_build else None,
        "compile_ms": round(first_build.compile_ms, 1) if first_build else None,
        # ZeRO-1 accounting: per-replica optimizer-state residency (moments
        # + fp32 masters; ~1/dp of the replicated figure when the sharded
        # update kicked in) and host-side argument-assembly ms per replay
        "opt_state_bytes_per_replica": opt_state_bytes_per_replica(opt),
        "zero1": acc.state.zero1_enabled,
        "arg_assembly_ms": (
            round(arg_assembly_ms, 3) if arg_assembly_ms is not None else None
        ),
        # dp-collective compression (docs/compression.md): the primary run's
        # active policy + its analytic per-step dp-axis wire bytes (None when
        # zero1/dp>1 is off — no dp collective pair exists)
        "compression_policy": acc._compression.name,
    }
    summary = opt.optimizer.compression_summary()
    result["dp_collective_bytes"] = (
        summary["dp_collective_bytes"] if summary else None
    )
    if profile_n:
        # device-time attribution of the sampled replay steps (builds are
        # compile events — their windows measure XLA, not the step).
        # Fail-soft: a backend whose trace comes back empty (no device op
        # events) produced no records, and the fields say so with None
        built_steps = {r.step for r in acc.telemetry.timeline.records() if r.built}
        samples = [
            d for d in acc.telemetry.device_records
            if d.step not in built_steps and d.busy_ms > 0
        ]
        result["profile_every_n"] = profile_n
        result["device_samples"] = len(samples)
        result["device_step_ms"] = (
            round(sum(d.busy_ms for d in samples) / len(samples), 3)
            if samples else None
        )
        result["device_collective_ms"] = (
            round(sum(d.collective_ms for d in samples) / len(samples), 3)
            if samples else None
        )
        result["device_collective_share"] = (
            round(sum(d.collective_share for d in samples) / len(samples), 4)
            if samples else None
        )
        mfus = [d.mfu for d in samples if d.mfu is not None]
        result["mfu"] = round(sum(mfus) / len(mfus), 4) if mfus else None
    # optional blocks, each behind its BENCH_<NAME>=0 switch.  A block that
    # raises leaves its <name>_error row and the run goes on — and exits
    # non-zero at the end.
    blocks = (
        # per-policy A/B rows (none/int8/fp8), docs/compression.md
        ("BENCH_COMPRESSION", "compression_ab", _compression_ab_block),
        # always-on flight-recorder overhead A/B, docs/telemetry.md
        ("BENCH_FLIGHTREC", "flightrec_ab", _flightrec_ab_block),
        # cold vs warm first step against a fresh AOT store, docs/aot_cache.md
        ("BENCH_AOT_CACHE", "aot_cache", _aot_cache_block),
        # continuous-batching decode under a Poisson trace, docs/serving.md
        ("BENCH_SERVING", "serving", _serving_block),
        # survive-and-resize rehearsal, docs/elastic.md
        ("BENCH_ELASTIC", "elastic", _elastic_block),
        # per-kernel on/off A/B, docs/kernels.md
        ("BENCH_KERNELS", "kernels_ab", _kernels_ab_block),
        # fused vs interleaved 1F1B A/B, docs/parallel_plan.md
        ("BENCH_PIPELINE", "pipeline", _pipeline_block),
    )
    for switch, label, block in blocks:
        if os.environ.get(switch, "1") == "0":
            result[f"{label}_skipped"] = f"disabled via {switch}=0"
            continue
        try:
            result.update(block(on_accel))
        except Exception as exc:
            _record_failure(result, label, exc)
    _PRIMARY_RESULT.update(result)
    # secondary BASELINE.md workloads, gated so the default driver run stays
    # inside its time budget (each adds a multi-minute cold compile)
    if os.environ.get("BENCH_FULL", "") == "1":
        # stderr progress marks: when the deadline watchdog cuts the extras,
        # the log shows which workload ate the time (each also reports its
        # own *_compile_s in the JSON when it completes).
        # BENCH_EXTRAS="bert,opt" selects a subset — the lever for staggering
        # extras across short chip windows; BERT first,
        # it is the BASELINE.json primary metric.
        extras = [
            ("bert", _bert_mrpc_workload),
            ("fp8", _fp8_ab_workload),
            ("bigmodel", _big_model_inference_workload),
            ("llama", _llama_fsdp_workload),
            ("opt", _opt_inference_workload),
            ("longctx", _long_context_workload),
            ("window", _sliding_window_workload),
        ]
        selected = os.environ.get("BENCH_EXTRAS")
        if selected:
            wanted = {s.strip() for s in selected.split(",") if s.strip()}
            known = {l for l, _ in extras}
            for typo in sorted(wanted - known):
                # a silently-dropped typo would burn the chip window the
                # variable exists to protect — flag it in the artifact
                result[f"extras_unknown_{typo}"] = f"not one of {sorted(known)}"
                print(f"[bench] unknown BENCH_EXTRAS entry {typo!r}", file=sys.stderr)
            extras = [(l, w) for l, w in extras if l in wanted]
        # don't START an extra that can't plausibly finish: a multi-minute
        # cold compile inside the last seconds of budget starves every
        # later row AND loses its own
        min_s = float(os.environ.get("BENCH_EXTRA_MIN_S", 300))
        _persist_partial(result)
        for label, workload in extras:
            if _remaining_s() < min_s:
                result[f"{label}_skipped"] = (
                    f"only {_remaining_s():.0f}s of budget left (< {min_s:.0f})"
                )
                _PRIMARY_RESULT.update(result)
                _persist_partial(result)
                continue
            t_extra = time.perf_counter()
            print(f"[bench] extra '{label}' start", file=sys.stderr, flush=True)
            try:
                result.update(workload(on_accel))
            except Exception as exc:  # keep the primary metric; exit non-zero
                _record_failure(result, label, exc)
            print(
                f"[bench] extra '{label}' done in {time.perf_counter() - t_extra:.1f}s",
                file=sys.stderr, flush=True,
            )
            # a watchdog cut after this point still reports the finished rows
            _PRIMARY_RESULT.update(result)
            _persist_partial(result)
    sys.exit(_emit_result(result, on_accel))


def _emit_result(result: dict, on_accel: bool) -> int:
    """Print the one JSON line; the exit code is non-zero if any phase left
    an ``_error`` row.  A CPU rehearsal's rows are renamed for what they
    are: nothing measured off the chip is printed under a per_chip name."""
    failed = sorted(k for k in result if k.endswith("_error"))
    if failed:
        result["failed_phases"] = failed
    if not on_accel:
        result = {k.replace("_per_chip", "_cpu_rehearsal"): v for k, v in result.items()}
        result["metric"] = result["metric"].replace("_per_chip", "_cpu_rehearsal").replace(
            "gpt2_small", "gpt2_tiny"
        )
        result.pop("vs_baseline", None)  # a TPU-vs-A100 ratio of a CPU run
    _emit_once(result)
    return 1 if failed else 0


if __name__ == "__main__":
    main()
