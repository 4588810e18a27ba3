"""Pallas hot-path kernels (native/kernels/, docs/kernels.md).

The contract under test: with ``KernelKwargs``/``$ACCELERATE_KERNELS``
arming a kernel, the armed path is **bitwise-identical** to its reference
path under jit (interpreter mode on CPU — the tier-1 surface), the
lowered IR proves the fusion structurally (``native/kernels/inspect.py``),
replays stay zero-recompile, the AOT-cache fingerprint keys on the policy,
and the default-off path is byte-identical to the pre-kernel library.  The
decode program's paged attention is behind no policy: it is held to
``cached_attention`` on the gathered span, to its dtype's rounding.

Runs on any virtual CPU mesh extent: the default suite forces 8 devices
(tests/conftest.py) and ``make multichip`` re-runs this file at dp=4.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
from accelerate_tpu.native.kernels import (
    KernelPolicy,
    _reset_active_kernels,
    _set_active_kernels,
    current_kernel_policy,
    resolve_kernel_policy,
)
from accelerate_tpu.native.kernels import inspect as kernel_inspect
from accelerate_tpu.native.kernels.collective_matmul import (
    collective_matmul,
    ring_all_gather,
    zero1_gather_eligible,
)
from accelerate_tpu.native.kernels.paged_attention import paged_attention
from accelerate_tpu.native.kernels.quantize_rs import (
    fused_quantize_dequantize,
    fused_reduce_scatter,
    stochastic_quantize_dequantize,
)
from accelerate_tpu.parallel import compress
from accelerate_tpu.parallel.mesh import make_mesh

P = jax.sharding.PartitionSpec


@pytest.fixture(autouse=True)
def _fresh():
    Accelerator._reset_state()
    _reset_active_kernels()
    nn.manual_seed(0)
    yield
    Accelerator._reset_state()
    _reset_active_kernels()


def _dp_mesh():
    # the package's own mesh: a bare jax.make_mesh has Explicit axes, which
    # with_sharding_constraint refuses
    return make_mesh({"dp": len(jax.devices())}, axis_order=("dp",))


# ---------------------------------------------------------------------------
# policy surface
# ---------------------------------------------------------------------------
def test_policy_default_off(monkeypatch):
    monkeypatch.delenv("ACCELERATE_KERNELS", raising=False)
    policy = resolve_kernel_policy()
    assert not policy.enabled
    assert policy.describe() == "none"
    assert current_kernel_policy() is None


def test_policy_resolution_env_kwargs_and_errors(monkeypatch):
    monkeypatch.setenv("ACCELERATE_KERNELS", "quantized_rs, collective_matmul")
    env_policy = resolve_kernel_policy()
    assert env_policy.armed() == ("collective_matmul", "quantized_rs")
    assert resolve_kernel_policy(KernelKwargs(kernels="all")).armed() == (
        "collective_matmul", "quantized_rs",
    )
    # explicit kwargs beat the env (the handler never reads it when set)
    assert not resolve_kernel_policy(KernelKwargs(kernels="none")).enabled
    for gone in ("flash_decode", "paged_attention"):  # the latter is no switch any more
        with pytest.raises(ValueError, match="unknown kernel"):
            resolve_kernel_policy(KernelKwargs(kernels=gone))
    # the env-armed policy is visible process-wide without an Accelerator
    assert current_kernel_policy() is not None
    # ...but an Accelerator's EXPLICIT disarm beats the env: a later bare
    # Optimizer must not re-arm a policy the user opted out of (the active
    # slot distinguishes disarmed from never-resolved)
    _set_active_kernels(None)
    assert current_kernel_policy() is None
    _reset_active_kernels()
    assert current_kernel_policy() is not None


def test_policy_interpret_resolves_off_tpu():
    assert resolve_kernel_policy(KernelKwargs(kernels="all")).interpret is True
    forced = resolve_kernel_policy(KernelKwargs(kernels="all", interpret=False))
    assert forced.interpret is False
    # the cache tag carries the lowering mode (a forced flip must be a
    # loud executable-cache miss, never a cross-mode replay); off = none
    assert forced.cache_tag().endswith(":mosaic")
    assert resolve_kernel_policy(
        KernelKwargs(kernels="all")
    ).cache_tag().endswith(":interpret")
    assert KernelPolicy().cache_tag() == "none"


def test_fingerprint_keys_on_kernel_policy():
    from accelerate_tpu.native.aot_cache import (
        fingerprint_mismatch,
        topology_fingerprint,
    )

    mesh = _dp_mesh()
    off = topology_fingerprint(mesh=mesh, compression="none", kernels="none")
    on = topology_fingerprint(
        mesh=mesh, compression="none", kernels="collective_matmul+quantized_rs"
    )
    assert off != on
    cause = fingerprint_mismatch(off, on)
    assert "kernels" in cause and "collective_matmul" in cause


# ---------------------------------------------------------------------------
# kernel 1: collective matmul / ring gather
# ---------------------------------------------------------------------------
def test_ring_gather_bitwise_vs_source():
    mesh = _dp_mesh()
    n = mesh.shape["dp"]
    w = jax.random.normal(jax.random.PRNGKey(1), (8 * n, 24), jnp.float32)
    sharding = jax.sharding.NamedSharding(mesh, P("dp", None))
    w_sharded = jax.device_put(w, sharding)
    gathered = jax.jit(lambda a: ring_all_gather(a, sharding, 0))(w_sharded)
    np.testing.assert_array_equal(np.asarray(gathered), np.asarray(w))
    assert zero1_gather_eligible(sharding, 0)
    assert not zero1_gather_eligible(sharding, 1)  # unsharded axis: no ring
    assert not zero1_gather_eligible(None, 0)


def test_collective_matmul_matches_reference():
    mesh = _dp_mesh()
    n = mesh.shape["dp"]
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 8 * n), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (8 * n, 16), jnp.float32)
    w_sharded = jax.device_put(
        w, jax.sharding.NamedSharding(mesh, P("dp", None))
    )
    got = jax.jit(lambda x, w: collective_matmul(x, w, mesh=mesh, interpret=True))(x, w_sharded)
    # ring accumulation order != monolithic dot order: allclose by design
    # (docs/kernels.md §numerics) — the bitwise contract lives on the
    # ZeRO-1 writeback ring, pinned above and end-to-end below
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ w), rtol=1e-5, atol=1e-5
    )


def test_ir_collective_matmul_fused():
    facts = kernel_inspect.check_collective_matmul(mesh=_dp_mesh())
    assert facts["fused_has_all_gather"] is False
    assert facts["fused_permute_hops"] >= 1
    assert facts["pallas_partial_dot_in_jaxpr"] is True


# ---------------------------------------------------------------------------
# kernel 2: fused quantize + reduce-scatter
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wire", [jnp.int8, jnp.float8_e4m3fn])
def test_fused_qdq_bitwise_vs_reference(wire):
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 64), jnp.float32) * 7.3
    ref = jax.jit(lambda x: compress.dequantize(*compress.quantize(x, 0, wire)))(x)
    fused = jax.jit(lambda x: fused_quantize_dequantize(x, 0, wire, interpret=True))(x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))


@pytest.mark.parametrize(
    "shape, axis, blocks",
    [((768, 1024), 1, 4), ((1000, 768), 0, 3), ((3, 600, 256), 1, 2)],
    ids=["last-axis", "ragged-rows", "ragged-middle"],
)
def test_fused_qdq_gridded_blocks_are_bitwise_too(shape, axis, blocks):
    """A leaf too big for one VMEM window runs as a grid over blocks of the
    scaled axis (what lets the kernel compile at GPT-2-small widths,
    tests/test_tpu_compile.py).  Slices along that axis never see each
    other, so the grid — ragged last block included — is exact."""
    from accelerate_tpu.native.kernels.quantize_rs import _block_len

    step = _block_len(shape, axis)
    assert step is not None and -(-shape[axis] // step) == blocks
    x = jax.random.normal(jax.random.PRNGKey(5), shape, jnp.float32) * 3.1
    ref = jax.jit(lambda x: compress.dequantize(*compress.quantize(x, axis, jnp.int8)))(x)
    fused = jax.jit(
        lambda x: fused_quantize_dequantize(x, axis, jnp.int8, interpret=True)
    )(x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))


def test_fused_reduce_scatter_residual_evolution_bitwise():
    """The whole EF recurrence — used = wire + err, err' = truth − wire —
    must evolve bitwise-identically through the fused kernel across steps."""
    mesh = _dp_mesh()
    n = mesh.shape["dp"]
    # the canonical spelling (parallel.sharding.canonical_spec): program
    # outputs come back without trailing Nones, and specs compare by spelling
    sharding = jax.sharding.NamedSharding(mesh, P("dp"))
    policy = compress.Int8Compression(min_size=1, min_block=1)
    shape = (4 * n, 32)

    def ref_step(g, err):
        return policy.reduce_scatter(g, sharding, 0, err)

    def fused_step(g, err):
        return fused_reduce_scatter(g, sharding, 0, err, policy, interpret=True)

    err_ref = jax.device_put(jnp.zeros(shape, jnp.float32), sharding)
    err_fused = err_ref
    for step in range(3):
        g = jax.random.normal(jax.random.PRNGKey(10 + step), shape, jnp.float32)
        used_ref, err_ref = jax.jit(ref_step)(g, err_ref)
        used_fused, err_fused = jax.jit(fused_step)(g, err_fused)
        np.testing.assert_array_equal(np.asarray(used_ref), np.asarray(used_fused))
        np.testing.assert_array_equal(np.asarray(err_ref), np.asarray(err_fused))
    # the residual stayed on the dp-sharded state layout
    assert err_fused.sharding.spec == sharding.spec


def test_ir_quantize_rs_fused():
    facts = kernel_inspect.check_quantize_rs()
    assert facts["narrow_payload_in_ir"] is True
    assert facts["round_inside_kernel_region"] is True


def test_stochastic_wire_deterministic_and_unbiased():
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 256), jnp.float32)
    key = jax.random.PRNGKey(7)
    a = jax.jit(lambda x: stochastic_quantize_dequantize(x, 0, key, interpret=True))(x)
    b = jax.jit(lambda x: stochastic_quantize_dequantize(x, 0, key, interpret=True))(x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # replay-stable
    # unbiased: the mean over many keys converges on x, beating the
    # deterministic round's fixed error
    rounds = [
        np.asarray(
            jax.jit(
                lambda x, k: stochastic_quantize_dequantize(x, 0, k, interpret=True)
            )(x, jax.random.PRNGKey(i))
        )
        for i in range(48)
    ]
    sr_err = np.abs(np.mean(rounds, axis=0) - np.asarray(x)).max()
    det = np.asarray(jax.jit(lambda x: fused_quantize_dequantize(x, 0, jnp.int8, interpret=True))(x))
    det_err = np.abs(det - np.asarray(x)).max()
    assert sr_err < det_err


# ---------------------------------------------------------------------------
# the decode program's attention: each slot's live pages, as they lie
# ---------------------------------------------------------------------------
class _AttnCfg:
    def __init__(self, sliding_window=0):
        self.sliding_window = sliding_window


def _span_attention(q, k_rows, v_rows, tables, positions, first_row, cfg, n_kv):
    """The plain reference: gather every slot's whole table as one contiguous
    ``(Hkv, S, d)`` cache and run ``cached_attention`` over the full span
    under its mask — what the decode program did before the kernel."""
    from accelerate_tpu.models.generation import cached_attention

    d = q.shape[-1]

    def one(q_s, row, p_s):
        def span(rows):
            return rows[first_row + row][..., : n_kv * d].reshape(-1, n_kv, d).transpose(1, 0, 2)

        att = cached_attention(
            q_s[None, :, None, :], span(k_rows)[None], span(v_rows)[None], p_s[None], cfg
        )
        return att[0, :, 0]

    return jax.vmap(one)(q, tables, positions)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "heads, n_kv, d, block, bps, window",
    [(25, 25, 64, 16, 20, 0), (32, 2, 128, 16, 12, 0), (8, 2, 16, 8, 40, 70)],
    ids=["25x64-multi-head", "32on2x128-grouped", "sliding-window"],
)
def test_paged_attention_is_cached_attention_over_the_live_pages(heads, n_kv, d, block, bps, window, dtype):
    """The kernel (interpreter) against ``cached_attention`` on the gathered
    span, at the shapes that run it, with ragged slot lengths: 1, one ending
    exactly on a page edge, one just past a chunk's edge, the longest the
    table allows, and a dead slot (zeros).  NaN fills the trash block and every page past a
    slot's length — the kernel's pool only: none may reach a live output.
    The last slot is fed a position PAST its table (an overrun micro-step of
    ``decode_steps > 1`` at capacity): it attends as the table's last position
    does, and reads no table entry past its row (on the chip that entry would
    be a DMA address).  Float32 to float32 rounding, bfloat16 to its own."""
    from accelerate_tpu.serving.kv_blocks import page_lanes

    lengths = [1, 3 * block, 128 + 3, bps * block, 0, block + 1, bps * block]  # tokens held; 0 = dead
    layers, layer, lanes = 2, 1, page_lanes(n_kv, d)
    num_blocks = 1 + sum(-(-n // block) for n in lengths) + 3
    rng = np.random.default_rng(0)

    def pool():
        rows = rng.standard_normal((layers * num_blocks, block, lanes)).astype(np.float32)
        rows[..., n_kv * d:] = 0.0  # the pad lanes hold zeros
        return rows

    k_rows, v_rows = pool(), pool()
    q = jnp.asarray(rng.standard_normal((len(lengths), heads, d)), dtype)
    tables = np.zeros((len(lengths), bps), np.int32)  # unused entries name the trash block
    free = iter(rng.permutation(np.arange(1, num_blocks)))  # out of order: the table does work
    for slot, n in enumerate(lengths):
        tables[slot, : -(-n // block)] = [next(free) for _ in range(-(-n // block))]
    last = [max(n - 1, 0) for n in lengths]
    positions = jnp.asarray(last[:-1] + [bps * block + 6], jnp.int32)  # the overrun
    first_row = jnp.int32(layer * num_blocks)
    cfg = _AttnCfg(window)

    def poisoned(rows):
        rows = rows.copy()
        dead = np.setdiff1d(np.arange(num_blocks), tables[tables > 0])  # trash + never handed out
        rows[layer * num_blocks + dead] = np.nan
        rows[: layer * num_blocks] = np.nan  # another layer's rows
        return jnp.asarray(rows, dtype)

    got = jax.jit(lambda *a: paged_attention(*a, cfg, n_kv=n_kv))(
        q, poisoned(k_rows), poisoned(v_rows), jnp.asarray(tables), positions, first_row
    )
    want = jax.jit(lambda *a: _span_attention(*a, cfg, n_kv))(
        q, jnp.asarray(k_rows, dtype), jnp.asarray(v_rows, dtype), jnp.asarray(tables),
        jnp.asarray(last, jnp.int32), first_row,
    )
    assert got.shape == (len(lengths), heads, d) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert not got[4].any()  # the dead slot: zeros
    live = [0, 1, 2, 3, 5, 6]
    tol = 2e-6 if dtype == jnp.float32 else 2e-2  # bfloat16: its 8 bits on values of 1-3
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# end-to-end: captured ZeRO-1 training parity
# ---------------------------------------------------------------------------
def _train(kernels, policy="none", steps=3, zero2=False):
    Accelerator._reset_state()
    _reset_active_kernels()
    nn.manual_seed(0)
    handlers = [TelemetryKwargs(enabled=True), CompressionKwargs(policy=policy)]
    if kernels:
        handlers.append(KernelKwargs(kernels=kernels))
    kwargs = {}
    if zero2:
        from accelerate_tpu import DataParallelPlugin

        kwargs["dp_plugin"] = DataParallelPlugin(zero1=True, zero2=True)
    acc = Accelerator(mixed_precision="bf16", kwargs_handlers=handlers, **kwargs)
    cfg = GPTConfig.tiny()
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
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(steps):
        ids = batch_to_global_array(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
            mesh=acc.mesh,
        )
        losses.append(float(step(ids)))
    state = {
        "losses": losses,
        "params": [np.asarray(p.data, np.float32) for p in opt.optimizer.param_list],
        "masters": [
            None if m is None else np.asarray(m) for m in opt.optimizer.master_params
        ],
        "residuals": [
            None if e is None else np.asarray(e)
            for e in getattr(opt.optimizer, "_comp_rs_err", [])
        ],
        "recompiles": acc.telemetry.recompiles_total,
        "kernel_records": list(acc.telemetry.kernel_records),
        "acc": acc,
        "opt": opt,
    }
    return state


def _assert_state_bitwise(a, b):
    assert a["losses"] == b["losses"]
    for x, y in zip(a["params"], b["params"]):
        np.testing.assert_array_equal(x, y)
    for x, y in zip(a["masters"], b["masters"]):
        if x is not None:
            np.testing.assert_array_equal(x, y)
    for x, y in zip(a["residuals"], b["residuals"]):
        if x is not None:
            np.testing.assert_array_equal(x, y)


def test_zero1_update_parity_collective_matmul():
    """Kernel 1's reference path: the whole ZeRO-1 captured update —
    params, masters, losses bitwise through the ring gather."""
    ref = _train(None)
    armed = _train("collective_matmul")
    _assert_state_bitwise(ref, armed)
    assert armed["recompiles"] == 0
    assert armed["opt"].optimizer._kernels is not None


def test_quantized_rs_parity_incl_residual_evolution():
    """Kernel 2's reference path: the int8 collective pair — losses,
    params AND the error-feedback residuals bitwise through the fused
    kernel."""
    ref = _train(None, policy="int8")
    armed = _train("quantized_rs", policy="int8")
    assert any(r is not None for r in armed["residuals"])
    _assert_state_bitwise(ref, armed)
    assert armed["recompiles"] == 0


def test_all_kernels_compose_zero_recompile():
    ref = _train(None, policy="int8")
    armed = _train("all", policy="int8", steps=4)
    assert armed["losses"][:3] == ref["losses"]
    assert armed["recompiles"] == 0
    assert [r.kernel for r in armed["kernel_records"]] == [
        "collective_matmul", "quantized_rs",
    ]
    assert all(
        r.stats.get("interpret") is True for r in armed["kernel_records"]
    )


def test_default_off_byte_identical():
    """$ACCELERATE_KERNELS unset: no kernel module on the hot path — the
    optimizer pins None, the capture-state pytree
    carries nothing new, and the run is bitwise the pre-kernel library
    (the parity tests above pin that by construction of `ref`)."""
    state = _train(None)
    assert state["opt"].optimizer._kernels is None
    assert state["acc"].kernels.enabled is False
    assert current_kernel_policy() is None
    # capture pytree: exactly the pre-kernel keys
    captured = state["opt"].optimizer.capture_state()
    assert set(captured) == {"opt_state", "master"}
    assert state["kernel_records"] == []


def test_zero2_stochastic_wire_trains_and_is_deterministic():
    """ZeRO-2 + int8 + quantized_rs arms the stochastic mid-accumulation
    wire: training stays sane (loss within the compression tolerance of
    the layout-only run) and identical seeds replay identical losses."""
    ref = _train(None, policy="int8", zero2=True)
    a = _train("quantized_rs", policy="int8", zero2=True)
    b = _train("quantized_rs", policy="int8", zero2=True)
    assert a["losses"] == b["losses"]  # replay-stable under capture
    assert a["acc"]._zero2_stochastic is True
    assert ref["acc"]._zero2_stochastic is False
    # the narrow wire honors the policy's eligibility gates: big matrices
    # ride it, tiny tensors (biases/norms under min_size) stay layout-only
    sr_flags = [sr_ok for (_, _, _, sr_ok) in a["acc"]._zero2_grads]
    assert any(sr_flags) and not all(sr_flags)
    for got, want in zip(a["losses"], ref["losses"]):
        assert abs(got - want) < 5e-2  # narrow wire, unbiased: close, not equal


def test_aot_cache_miss_names_kernel_policy(tmp_path):
    """An entry stored by a kernels-off process must MISS loudly — the
    ``kind="aot_cache"`` event's cause naming the ``kernels`` field — when
    the same program variant is looked up by a kernel-armed process."""
    import json

    from accelerate_tpu.native.aot_cache import (
        AOTCompilationCache,
        _digest,
        topology_fingerprint,
    )
    from accelerate_tpu.telemetry import Telemetry
    from accelerate_tpu.utils.dataclasses import (
        CompilationCacheKwargs,
        TelemetryKwargs,
    )

    cache_dir = tmp_path / "aot"
    cache_dir.mkdir()
    mesh = _dp_mesh()
    # the twin: same program variant, stored under the kernels-off topology
    off_fp = topology_fingerprint(mesh=mesh, compression="none", kernels="none")
    variant = "cafebabe0123"
    (cache_dir / f"{variant}-{_digest(off_fp)}.json").write_text(
        json.dumps({"fingerprint": off_fp})
    )
    cache = AOTCompilationCache(CompilationCacheKwargs(cache_dir=str(cache_dir)))
    cache.set_context(
        mesh=mesh, compression="none", kernels="collective_matmul+quantized_rs"
    )
    hub = Telemetry(TelemetryKwargs(enabled=True))
    cache.attach_telemetry(hub)
    assert cache.lookup(variant, cache.fingerprint(), "train", "k123") is None
    misses = [
        dict(e) for e in hub.aot_cache_events if e.get("event") == "miss"
    ]
    assert misses, list(hub.aot_cache_events)
    cause = str(misses[-1].get("cause", ""))
    assert "kernels" in cause and "collective_matmul" in cause, cause
