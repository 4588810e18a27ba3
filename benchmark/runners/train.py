"""Training cells: ``Accelerator.prepare`` + ``compile_step`` + AdamW on
sequences from the seed, fed by a prepared data loader.

Set-up builds ONE captured step with its state, drives it through the first
``check_steps`` steps by the window's own call and feed (these also compile and
warm the program), reads what ``correct`` needs from the optimizer's state, and
hands the same object to the window.  The reference follows those steps once
the window has closed, the peak has been read and the program's state is freed.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import compare, harness, traffic


def build(cell, seed: int, mixed_precision: str = None):
    """The program under test: model (weights from the seed), optimizer,
    prepared loader, captured step.  ``mixed_precision`` other than the
    configuration's is for reading the control only."""
    import jax.numpy as jnp

    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator, ParallelismConfig, TelemetryKwargs, prepare_data_loader

    cfg, mix = cell.config, cell.mix
    fsdp = mix["parallelism"]["fsdp"]
    accelerator = Accelerator(
        mixed_precision=mixed_precision or cfg["precision"]["mixed_precision"],
        parallelism_config=ParallelismConfig(fsdp_size=fsdp) if fsdp > 1 else None,
        kwargs_handlers=[TelemetryKwargs(enabled=True)],
    )
    params = cell.reference.init_params(cfg, seed, jnp.bfloat16)
    model = cell.family.build_model(cfg, params)
    del params
    o = mix["optimizer"]
    optimizer = optim.AdamW(
        model.parameters(), lr=o["lr"], betas=(o["b1"], o["b2"]), eps=o["eps"],
        weight_decay=o["weight_decay"],
    )
    rows = traffic.train_rows(mix, cfg["vocab_size"], seed)
    shards = accelerator.mesh.shape["dp"] * accelerator.mesh.shape["fsdp"]
    loader = prepare_data_loader(
        dataset=[{"input_ids": r, "labels": r} for r in rows],
        batch_size=mix["rows_per_step"] // shards,
    )
    model, optimizer, loader = accelerator.prepare(model, optimizer, loader)

    def train_step(batch):
        optimizer.zero_grad()
        out = model(batch["input_ids"], labels=batch["labels"])
        accelerator.backward(out["loss"])
        optimizer.step()
        return out["loss"]

    return {
        "accelerator": accelerator, "model": model, "optimizer": optimizer,
        "loader": loader, "step": accelerator.compile_step(train_step), "rows": rows,
    }


class Feed:
    """The window's own feed: ``next()`` on the prepared loader, epoch after
    epoch, under the ``loader.next`` span."""

    def __init__(self, loader):
        self.loader = loader
        self.it = iter(loader)
        self.wait_s = 0.0
        self.calls = 0

    def close(self):
        self.it.close()

    def next(self):
        t0 = time.perf_counter()
        with harness.span("loader.next"):
            try:
                batch = next(self.it)
            except StopIteration:
                self.it = iter(self.loader)
                batch = next(self.it)
        self.wait_s += time.perf_counter() - t0
        self.calls += 1
        return batch


def _find_mu(state):
    """Adam's first moment inside an optax state, wherever it is nested."""
    if hasattr(state, "mu"):
        return state.mu
    children = state if isinstance(state, (tuple, list)) else (
        [getattr(state, f) for f in getattr(state, "_fields", ())]
    )
    for child in children:
        found = _find_mu(child)
        if found is not None:
            return found
    return None


def _program_leaf_norms(cell, prog, arrays) -> dict:
    """``{canonical leaf: norm}`` of one array per program parameter."""
    import jax
    import jax.numpy as jnp

    names = [cell.family.canonical_name(n) for n, _ in cell.family.named_parameters(prog["model"])]

    def norms(xs):
        parts = [part for n, x in zip(names, xs) for part in cell.reference.split_leaf(n, x)]
        return {n: jnp.linalg.norm(x.astype(jnp.float32)) for n, x in parts}

    return {k: float(v) for k, v in jax.device_get(jax.jit(norms)(list(arrays))).items()}


def _optimizer_arrays(cell, prog, per_param):
    """The optimizer's per-parameter arrays in the order of the model's
    parameters (the optimizer holds them in the order it was given them)."""
    opt = prog["optimizer"].optimizer
    by_id = {id(p): x for p, x in zip(opt.param_list, per_param)}
    return [by_id[id(p)] for _, p in cell.family.named_parameters(prog["model"])]


def first_steps(cell, prog, feed, seed: int, step_fn=None) -> dict:
    """Drive the captured step through the first ``check_steps`` steps and read
    the losses, the first gradient's norms (from Adam's first moment after one
    step: m1 = (1 - b1) g) and the norms of the parameters' change (from the
    optimizer's float32 masters against the seed's weights)."""
    import jax
    import jax.numpy as jnp

    step_fn = step_fn or prog["step"]
    mix = cell.mix
    opt = prog["optimizer"].optimizer
    losses, grad_norms = [], None
    for i in range(mix["check_steps"]):
        loss = step_fn(feed.next())
        losses.append(float(loss))
        if i == 0:
            mu = _optimizer_arrays(cell, prog, _find_mu(opt.opt_state))
            m_norms = _program_leaf_norms(cell, prog, mu)
            grad_norms = {k: v / (1.0 - mix["optimizer"]["b1"]) for k, v in m_norms.items()}
    masters = [
        m if m is not None else p.data for m, p in zip(opt.master_params, opt.param_list)
    ]
    masters = _optimizer_arrays(cell, prog, masters)
    start = cell.reference.init_params(cell.config, seed, jnp.bfloat16)
    names = [cell.family.canonical_name(n) for n, _ in cell.family.named_parameters(prog["model"])]
    starts = [cell.family.leaf_of(start, n) for n in names]
    delta = jax.jit(
        lambda ms, ss: [m.astype(jnp.float32) - s.astype(jnp.float32) for m, s in zip(ms, ss)]
    )(masters, starts)
    update_norms = _program_leaf_norms(cell, prog, delta)
    return {"losses": losses, "grad_norms": grad_norms, "update_norms": update_norms}


def reference_steps(cell, seed: int, rows, precision="float32", drop_rows=0) -> dict:
    """The plain reference over the same first steps, from the same seed."""
    import jax.numpy as jnp

    mix = cell.mix
    n = mix["rows_per_step"]
    batches = [rows[i * n:(i + 1) * n] for i in range(mix["check_steps"])]
    params = cell.reference.init_params(cell.config, seed, jnp.float32)
    return cell.reference.train_steps(
        params, batches, cell.config["n_head"], mix["optimizer"], precision=precision,
        block_rows=mix["reference_block_rows"], drop_rows=drop_rows,
    )


def readings(cell, seed: int, kinds, seconds=None) -> dict:
    """What the limits are set from, for one seed: the numbers of the program
    against the reference, and of each of ``kinds`` put in the program's place:
    ``control`` (what the configuration names for training: ``program_<p>``, the
    program's own path at the precision below, or the reference in it),
    ``bf16`` (the reference in the configuration's own), ``half_batch`` (half of
    every batch left out, the mean taken over the rest).  Needs no window."""
    program, rows = _first_steps_alone(cell, seed)
    reference = reference_steps(cell, seed, rows)
    numbers, notes = compare.train_numbers(program, reference)
    out = {"program": numbers, "leaf_gaps": {"program": _leaf_gaps(notes)}}
    stand_ins = {
        "bf16": {"precision": "bfloat16"},
        "half_batch": {"drop_rows": cell.mix["rows_per_step"] // 2},
    }
    for kind in kinds:
        what = cell.config["precision"]["control"]["train"] if kind == "control" else kind
        if what.startswith("program_"):  # the program's own lower-precision path
            other, _ = _first_steps_alone(cell, seed, mixed_precision=what[len("program_"):])
        else:
            other = reference_steps(cell, seed, rows, **stand_ins.get(what, {"precision": what}))
        out[kind], notes = compare.train_numbers(other, reference)
        out["leaf_gaps"][kind] = _leaf_gaps(notes)
    return out


def _leaf_gaps(notes: dict) -> dict:
    return {"grad": notes["grad_leaf_gaps"], "update": notes["update_leaf_gaps"]}


def _first_steps_alone(cell, seed: int, mixed_precision: str = None) -> tuple:
    prog = build(cell, seed, mixed_precision)
    feed = Feed(prog["loader"])
    program = first_steps(cell, prog, feed, seed)
    rows = prog["rows"]
    feed.close()
    harness.free_program(prog)
    return program, rows


def timed(cell, seed: int, seconds: float, tracer, t_start: float, step_wrapper=None) -> dict:
    """Set-up and the measured window.  Returns plain host data only: every
    reference to the program's device state dies with this frame, so that the
    reference can have the chip's memory afterwards.

    With tracing on, the profiler runs over the window's last ``trace_seconds``;
    the counters the per-layer readers take are those of the part before it, so
    that the profiler's own cost is in neither."""
    import jax

    mix = cell.mix
    prog = build(cell, seed)
    step = step_wrapper(prog["step"], prog) if step_wrapper else prog["step"]
    feed = Feed(prog["loader"])
    harness.log("built; first steps")
    program = first_steps(cell, prog, feed, seed, step_fn=step)
    telemetry = prog["accelerator"].telemetry
    # one more step past the checked ones: the first replay on the carried-over
    # state layout has happened before the window opens
    jax.block_until_ready(step(feed.next()))
    recompiles_before = telemetry.recompiles_total
    feed.wait_s, feed.calls = 0.0, 0
    tokens_per_step = mix["rows_per_step"] * mix["seq_len"]

    def counters(now):
        return {
            "steps": steps, "window_s": now - t0, "tokens": steps * tokens_per_step,
            "dispatch_s": dispatch_s, "data_wait_s": feed.wait_s, "data_calls": feed.calls,
            "recompiles_in_window": telemetry.recompiles_total - recompiles_before,
            "rows_per_chip": mix["rows_per_step"] // cell.chips,
        }

    def drain():
        with harness.span("step.sync"):
            while pending:
                window_losses.append(float(pending.pop(0)))

    # -- the measured window ---------------------------------------------------
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    t_end = t0 + seconds
    trace_from = t_end - mix["trace_seconds"] if tracer.on else None
    pending, steps, dispatch_s, window_losses, untraced = [], 0, 0.0, [], None
    while time.perf_counter() < t_end:
        if trace_from is not None and untraced is None and time.perf_counter() >= trace_from:
            drain()
            untraced = counters(time.perf_counter())
            tracer.start()
        batch = feed.next()
        t_d = time.perf_counter()
        with harness.span("step.dispatch"):
            loss = step(batch)
        dispatch_s += time.perf_counter() - t_d
        pending.append(loss)
        steps += 1
        if len(pending) > mix["steps_in_flight"]:
            with harness.span("step.sync"):
                window_losses.append(float(pending.pop(0)))
    drain()
    whole = counters(time.perf_counter())
    tracer.stop()
    harness.log(f"window closed: {steps} steps in {whole['window_s']:.3f}s")
    out = {
        "program": program, "setup_s": setup_s, "whole": whole,
        "counters": untraced or whole, "window_losses": window_losses,
        "memory_peak_bytes": harness.memory_peak_bytes(), "rows": prog["rows"],
    }
    feed.close()
    harness.free_program(prog)
    return out


def run(cell, seed: int, seconds: float, trace: bool, t_start: float, device: dict,
        step_wrapper=None) -> dict:
    """One run.  ``step_wrapper`` lets a test break the timed path underneath
    (it gets the captured step and returns what is called in its place)."""
    import gc

    tracer = harness.TracedWindow(trace, cell.name)
    got = timed(cell, seed, seconds, tracer, t_start, step_wrapper)
    gc.collect()
    harness.log(f"program freed: {harness.memory_in_use_bytes()} bytes still in use")

    # -- correct: the reference follows the first steps ----------------------
    t_ref = time.perf_counter()
    reference = reference_steps(cell, seed, got["rows"])
    numbers, notes = compare.train_numbers(got["program"], reference)
    numbers["nonfinite_window_losses"] = float(
        sum(not np.isfinite(x) for x in got["window_losses"])
    )
    notes["reference_s"] = time.perf_counter() - t_ref
    shown = {k: v for k, v in notes.items() if not k.endswith("_leaf_gaps")}
    harness.log(f"reference done in {notes['reference_s']:.1f}s: {shown}")
    whole = got["whole"]
    return {
        "attempted": whole["steps"], "failed": 0,
        "metrics": {
            "train_tokens_per_s": whole["tokens"] / whole["window_s"],
            "setup_s": got["setup_s"],
        },
        "numbers": numbers, "counters": got["counters"],
        "device": dict(device, memory_peak_bytes=got["memory_peak_bytes"]),
        "tracer": tracer, "notes": notes,
    }
