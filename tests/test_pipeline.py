import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.parallel.pipeline import bubble_fraction, bubble_ticks, gpipe
from accelerate_tpu.state import AcceleratorState
from accelerate_tpu.utils.dataclasses import ParallelismConfig


def stage_fn(params, h):
    return jnp.tanh(h @ params["w"] + params["b"])


def make_stages(n_stages, dim, key=0):
    ks = jax.random.split(jax.random.key(key), n_stages)
    return {
        "w": jnp.stack([jax.random.normal(k, (dim, dim)) * 0.5 for k in ks]),
        "b": jnp.zeros((n_stages, dim)),
    }


def sequential(params, x):
    h = x
    for i in range(params["w"].shape[0]):
        h = stage_fn({"w": params["w"][i], "b": params["b"][i]}, h)
    return h


def test_gpipe_matches_sequential():
    state = AcceleratorState(parallelism_config=ParallelismConfig(pp_size=4, dp_size=2))
    params = make_stages(4, 16)
    x = jax.random.normal(jax.random.key(1), (8, 16))
    expected = sequential(params, x)
    out = jax.jit(
        lambda p, x_: gpipe(stage_fn, p, x_, num_microbatches=4, mesh=state.mesh)
    )(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-6)


def test_gpipe_differentiable():
    state = AcceleratorState(parallelism_config=ParallelismConfig(pp_size=4, dp_size=2))
    params = make_stages(4, 8)
    x = jax.random.normal(jax.random.key(2), (4, 8))

    def loss_pp(p):
        return gpipe(stage_fn, p, x, num_microbatches=2, mesh=state.mesh).sum()

    def loss_seq(p):
        return sequential(p, x).sum()

    g_pp = jax.grad(loss_pp)(params)
    g_seq = jax.grad(loss_seq)(params)
    np.testing.assert_allclose(np.asarray(g_pp["w"]), np.asarray(g_seq["w"]), rtol=1e-4, atol=1e-6)


def test_gpipe_pp1_fallback():
    state = AcceleratorState()  # pp == 1
    params = make_stages(3, 8)
    x = jax.random.normal(jax.random.key(3), (4, 8))
    out = gpipe(stage_fn, params, x, num_microbatches=2, mesh=state.mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(sequential(params, x)), rtol=1e-5)


def test_gpipe_bad_microbatch():
    state = AcceleratorState(parallelism_config=ParallelismConfig(pp_size=4, dp_size=2))
    params = make_stages(4, 8)
    with pytest.raises(ValueError):
        gpipe(stage_fn, params, jnp.ones((6, 8)), num_microbatches=4, mesh=state.mesh)


def test_bubble_profile_common_granularity():
    """Pin the A/B bubble accounting of a fused against an interleaved
    schedule: BOTH arms must be quoted in the SAME chunk unit (granularity=V), where
    the fused profile is exactly V× the interleaved one.  At each
    schedule's OWN default granularity the two are numerically equal
    (2·(S−1) self-sized chunks each) — comparing defaults would silently
    erase the interleaving gain, which is the bug this test pins out."""
    # the A/B geometry: M=8, S=2, V=2 quoted in 1/2-stage chunks
    assert bubble_ticks(8, 2, 1, granularity=2) == 4
    assert bubble_ticks(8, 2, 2, granularity=2) == 2
    for S in (2, 4):
        for V in (2, 3, 4):
            fused = bubble_ticks(8, S, 1, granularity=V)
            inter = bubble_ticks(8, S, V, granularity=V)
            assert fused == V * inter, (S, V, fused, inter)
            assert inter < fused, (S, V)
            # default granularity is the schedule's own chunk: both sides
            # collapse to 2*(S-1) and the comparison loses its meaning
            assert bubble_ticks(8, S, V) == bubble_ticks(8, S, 1) == 2 * (S - 1)
    # the analytic fraction carries the same monotone gain
    assert bubble_fraction(8, 2, 2) == bubble_fraction(8, 2, 1) / 2


# ---------------------------------------------------------------------------
# Pipelined GPT: real trunk through GPipe (pp) + ring attention (sp)
# ---------------------------------------------------------------------------
def test_pipelined_gpt_matches_plain_trunk():
    """The pp×sp pipelined trunk must equal a sequential per-layer apply."""
    import functools

    import accelerate_tpu.nn as nn
    from accelerate_tpu.models.gpt import (
        GPTConfig,
        _StackedBlocks,
        _pipelined_block,
    )

    nn.manual_seed(0)
    cfg = GPTConfig(vocab_size=256, n_positions=64, n_embd=32, n_layer=4, n_head=2)
    blocks = _StackedBlocks(cfg)
    stacked = {n: getattr(blocks, n).data for n in _StackedBlocks._ORDER}
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(4, 16, 32)).astype(np.float32)
    )
    body = functools.partial(
        _pipelined_block, n_head=2, eps=cfg.layer_norm_eps, seq_axis="sp"
    )

    from accelerate_tpu.parallel.mesh import shard_map_compat
    from jax.sharding import Mesh, PartitionSpec as P

    from accelerate_tpu.utils.constants import ALL_MESH_AXES

    mesh1 = Mesh(
        np.asarray(jax.devices()[:1]).reshape((1,) * len(ALL_MESH_AXES)),
        ALL_MESH_AXES,
    )

    def seq_apply(xv):
        h = xv
        for i in range(cfg.n_layer):
            h = body({k: v[i] for k, v in stacked.items()}, h)
        return h

    ref = np.asarray(
        shard_map_compat(seq_apply, mesh=mesh1, in_specs=(P(),), out_specs=P())(x)
    )

    # pp2 × sp2 × dp2: layers span stages (2 per stage), seq rides the ring
    mesh8 = Mesh(
        np.asarray(jax.devices()).reshape(2, 1, 1, 2, 1, 2),
        ("dp", "fsdp", "tp", "sp", "ep", "pp"),
    )
    got = np.asarray(
        gpipe(body, stacked, x, num_microbatches=2, mesh=mesh8, seq_axis="sp")
    )
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_pipelined_gpt_trains_on_pp_sp_mesh():
    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, PipelinedGPTLMHeadModel

    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(parallelism_config=ParallelismConfig(sp_size=2, pp_size=2))
    cfg = GPTConfig(vocab_size=256, n_positions=64, n_embd=32, n_layer=4, n_head=2)
    model = PipelinedGPTLMHeadModel(cfg, num_microbatches=2)
    opt = optim.AdamW(model.parameters(), lr=1e-3)
    model, opt = acc.prepare(model, opt)

    # stacked block params must ride the pp axis
    spec = model.blocks.qkv_w.data.sharding.spec
    assert spec and spec[0] == "pp", f"layer stack not pp-sharded: {spec}"

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, size=(8, 32)), jnp.int32
    )
    gb = batch_to_global_array(ids, mesh=acc.mesh)
    losses = [float(step(gb)) for _ in range(4)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses
    Accelerator._reset_state()
