import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu.ops.attention import sdpa_reference
from accelerate_tpu.ops.ring_attention import ring_attention
from accelerate_tpu.state import AcceleratorState
from accelerate_tpu.utils.dataclasses import ParallelismConfig


def _setup(sp=4, dp_extra=2):
    state = AcceleratorState(parallelism_config=ParallelismConfig(sp_size=sp, dp_size=dp_extra))
    return state.mesh


def _place(x, mesh):
    return jax.device_put(x, NamedSharding(mesh, P("dp", None, "sp", None)))


@pytest.mark.parametrize("is_causal", [False, True])
def test_ring_attention_matches_reference(is_causal):
    mesh = _setup()
    b, h, s, d = 2, 2, 32, 8
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), dtype=jnp.float32)
    expected = sdpa_reference(q, k, v, is_causal=is_causal)
    qs, ks_, vs = _place(q, mesh), _place(k, mesh), _place(v, mesh)
    out = jax.jit(
        lambda a, b_, c: ring_attention(a, b_, c, mesh=mesh, is_causal=is_causal)
    )(qs, ks_, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-5)


def test_ring_attention_grads_match():
    mesh = _setup()
    b, h, s, d = 2, 2, 32, 8
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, h, s, d))
    v = jax.random.normal(ks[2], (b, h, s, d))

    def ring_loss(q_, k_, v_):
        return ring_attention(q_, k_, v_, mesh=mesh, is_causal=True).sum()

    def ref_loss(q_, k_, v_):
        return sdpa_reference(q_, k_, v_, is_causal=True).sum()

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(_place(q, mesh), _place(k, mesh), _place(v, mesh))
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, ge in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(ge), rtol=5e-4, atol=1e-5)


def test_ring_attention_sp1_fallback():
    state = AcceleratorState()  # sp == 1 → plain attention path
    q = jax.random.normal(jax.random.key(0), (1, 2, 16, 8))
    out = ring_attention(q, q, q, mesh=state.mesh, is_causal=True)
    expected = sdpa_reference(q, q, q, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-5)


@pytest.mark.parametrize("is_causal", [False, True])
def test_ring_flash_hop_path_matches_reference(is_causal, monkeypatch):
    """The TPU hop-kernel ring path (forced on CPU via interpret mode):
    parity with monolithic attention, forward and backward."""
    import accelerate_tpu.ops.ring_attention as ra

    monkeypatch.setattr(ra, "_FORCE_FLASH_HOPS", True)

    mesh = _setup(sp=2, dp_extra=4)
    b, h, s, d = 1, 1, 256, 64  # chunk 128 per sp shard: one full MXU tile
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), dtype=jnp.float32)
    expected = sdpa_reference(q, k, v, is_causal=is_causal)

    spec = NamedSharding(mesh, P(None, None, "sp", None))
    qs, ks_, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = jax.jit(
        lambda a, b_, c: ring_attention(
            a, b_, c, mesh=mesh, is_causal=is_causal, batch_axes=()
        )
    )(qs, ks_, vs)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4
    )

    def ring_loss(q_, k_, v_):
        return (
            ring_attention(q_, k_, v_, mesh=mesh, is_causal=is_causal, batch_axes=())
            * jnp.arange(d)
        ).sum()

    def ref_loss(q_, k_, v_):
        return (sdpa_reference(q_, k_, v_, is_causal=is_causal) * jnp.arange(d)).sum()

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(qs, ks_, vs)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, ge in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(ge), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) mode
# ---------------------------------------------------------------------------
def test_ulysses_matches_reference():
    from accelerate_tpu.ops.ring_attention import ulysses_attention

    mesh = _setup(sp=4, dp_extra=2)
    rng = np.random.default_rng(0)
    b, h, s, d = 2, 4, 64, 16
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    for causal in (True, False):
        want = sdpa_reference(q, k, v, is_causal=causal)
        got = jax.jit(
            lambda q, k, v: ulysses_attention(
                _place(q, mesh), _place(k, mesh), _place(v, mesh),
                mesh=mesh, is_causal=causal,
            )
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_grads_match_reference():
    from accelerate_tpu.ops.ring_attention import ulysses_attention

    mesh = _setup(sp=4, dp_extra=2)
    rng = np.random.default_rng(1)
    b, h, s, d = 2, 4, 64, 16
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_want = jax.grad(
        loss(lambda q, k, v: sdpa_reference(q, k, v, is_causal=True)), argnums=(0, 1, 2)
    )(q, k, v)
    g_got = jax.jit(
        jax.grad(
            loss(
                lambda q, k, v: ulysses_attention(
                    _place(q, mesh), _place(k, mesh), _place(v, mesh),
                    mesh=mesh, is_causal=True,
                )
            ),
            argnums=(0, 1, 2),
        )
    )(q, k, v)
    for a, b_ in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=3e-5)


def test_ulysses_falls_back_when_heads_not_divisible():
    from accelerate_tpu.ops.ring_attention import ulysses_attention

    mesh = _setup(sp=4, dp_extra=2)
    rng = np.random.default_rng(2)
    b, h, s, d = 2, 3, 64, 16  # 3 heads % sp=4 != 0 -> ring fallback
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    want = sdpa_reference(q, k, v, is_causal=True)
    got = ulysses_attention(
        _place(q, mesh), _place(k, mesh), _place(v, mesh), mesh=mesh, is_causal=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_sequence_parallel_attention_dispatch():
    from accelerate_tpu.ops import ring_attention as ra

    mesh = _setup(sp=2, dp_extra=4)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((4, 4, 32, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((4, 4, 32, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((4, 4, 32, 16)), jnp.float32)
    want = sdpa_reference(q, k, v, is_causal=True)
    for mode in ("ring", "all_to_all"):
        got = ra.sequence_parallel_attention(
            _place(q, mesh), _place(k, mesh), _place(v, mesh),
            mesh=mesh, is_causal=True, mode=mode,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_pipelined_gpt_trains_with_all_to_all_mode():
    """SequenceParallelPlugin(mode='all_to_all') is honored by the trunk."""
    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, PipelinedGPTLMHeadModel
    from accelerate_tpu.utils.dataclasses import SequenceParallelPlugin

    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(
        parallelism_config=ParallelismConfig(sp_size=2, pp_size=2),
        sp_plugin=SequenceParallelPlugin(mode="all_to_all"),
        mixed_precision="bf16",
    )
    cfg = GPTConfig.tiny()
    model = PipelinedGPTLMHeadModel(cfg, num_microbatches=2)
    opt = optim.AdamW(model.parameters(), lr=1e-3)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 1024, (4, 64), dtype=np.int32)
    )
    batch = batch_to_global_array(ids, mesh=acc.mesh)
    l1 = float(step(batch))
    l2 = float(step(batch))
    assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1
