"""1F1B fused pipeline schedule: gradient parity with GPipe + memory window.

Round-2 verdict Missing #4: GPipe fill-drain holds num_microbatches stage
inputs alive through the backward; the reference gets 1F1B from
megatron.core's get_forward_backward_func (reference utils/megatron_lm.py:40,
train_step :1035).  Here 1F1B is a fused fwd+bwd shard_map loop
(parallel/pipeline.py): loss computed inside the last stage, cotangents hop
down-ring while later microbatches still flow up, and each stage stores only
``2·S−1`` inputs regardless of M.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import accelerate_tpu.nn as nn
import accelerate_tpu.optim as optim
from accelerate_tpu import Accelerator, ParallelismConfig
from accelerate_tpu.data_loader import batch_to_global_array
from accelerate_tpu.models import GPTConfig, PipelinedGPTLMHeadModel
from accelerate_tpu.parallel.pipeline import (
    bubble_fraction,
    bubble_ticks,
    residual_window,
    schedule_ticks,
)
from accelerate_tpu.utils.dataclasses import PipelineParallelPlugin


def test_memory_window_beats_gpipe_at_m8_s2():
    """At M=8, S=2 the 1F1B window is 3 stage inputs vs GPipe's 8."""
    assert residual_window(2) == 3
    assert residual_window(4) == 7
    # bubble profile: M + 2S - 2 fused cycles (each = 1 fwd + 1 bwd slot)
    assert schedule_ticks(8, 2) == 10


def test_interleaved_profile_m8_s2_v2():
    """The virtual factor's analytic profile (ISSUE 15 acceptance): at
    M=8, S=2, V=2 the interleaved schedule shows STRICTLY fewer bubble
    ticks than the fused one (compared in a common chunk granularity),
    the bubble fraction drops from (S−1)/M to (S−1)/(V·M), the lockstep
    trip count is M·V + S·V + S − 2 chunk ticks, and the residual window
    keeps the 2·S−1 order per hosted span (V·(2S−1) chunk inputs, each
    1/V the fused activation)."""
    fused = bubble_ticks(8, 2, virtual=1, granularity=2)
    interleaved = bubble_ticks(8, 2, virtual=2, granularity=2)
    assert interleaved < fused, (interleaved, fused)
    assert (fused, interleaved) == (4, 2)
    assert bubble_fraction(8, 2, 2) < bubble_fraction(8, 2, 1)
    assert bubble_fraction(8, 2, 2) == (2 - 1) / (2 * 8)
    assert schedule_ticks(8, 2, virtual=2) == 20
    assert residual_window(2, virtual=2) == 6
    # degenerate V=1 reproduces the fused profile exactly
    assert schedule_ticks(8, 2, virtual=1) == schedule_ticks(8, 2)
    assert residual_window(2, virtual=1) == residual_window(2)


def _plain_params(acc, model):
    """Param dict in PLAIN layer order: prepare() commits the interleave
    permutation physically at V>1 (docs/parallel_plan.md §layout contract),
    so cross-schedule comparisons view committed stacks through the plan's
    inverse order.  No-op for uncommitted (plain) runs."""
    stage = acc.plan.stage
    out = {}
    for n, p in model.named_parameters():
        a = np.asarray(p.data)
        if getattr(p, "_layer_layout_committed", False) and stage is not None:
            a = a[np.asarray(stage.inverse_layer_order(a.shape[0]))]
        out[n] = a
    return out


def _train(schedule: str, steps: int = 3, microbatches: int = 8,
           n_layer: int = 2, virtual: int = 0, layout: str = None):
    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(
        parallelism_config=ParallelismConfig(pp_size=2),
        pp_plugin=PipelineParallelPlugin(
            pp_size=2, num_microbatches=microbatches, schedule=schedule,
            virtual_stages=virtual, layout=layout,
        ),
        mixed_precision="no",
    )
    cfg = GPTConfig.tiny()
    if n_layer != cfg.n_layer:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, n_layer=n_layer)
    model = PipelinedGPTLMHeadModel(cfg, num_microbatches=microbatches)
    opt = optim.SGD(model.parameters(), lr=0.1)
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
            np.random.default_rng(0).integers(0, 1024, (32, 32)), jnp.int32
        ),
        mesh=acc.mesh,
    )
    losses = [float(step(ids)) for _ in range(steps)]
    return losses, _plain_params(acc, model)


def test_loss_and_grad_parity_with_gpipe():
    """Same init, same data: 1F1B must train identically to GPipe — loss
    trajectory AND updated parameters (grads) agree."""
    l_g, p_g = _train("gpipe")
    l_f, p_f = _train("1f1b")
    np.testing.assert_allclose(l_f, l_g, rtol=2e-5, atol=2e-5)
    for name in p_g:
        np.testing.assert_allclose(
            p_f[name], p_g[name], rtol=3e-4, atol=3e-5, err_msg=name
        )


def test_ignore_index_parity():
    """-100 padded labels must drop out of the fused loss exactly like the
    gpipe path's F.cross_entropy ignore_index."""
    import jax

    from accelerate_tpu.models.gpt import (
        _pure_lm_head_loss,
        lm_shift_loss,
    )
    from accelerate_tpu.nn import Tensor

    rng = np.random.default_rng(0)
    b, s, c, v = 2, 8, 16, 32
    h = jnp.asarray(rng.normal(size=(b, s, c)), jnp.float32)
    labels = rng.integers(0, v, (b, s)).astype(np.int32)
    labels[:, -3:] = -100  # padded tail
    ln_w, ln_b = jnp.ones((c,)), jnp.zeros((c,))
    head_w = jnp.asarray(rng.normal(size=(v, c)), jnp.float32)
    lsum, w = _pure_lm_head_loss(
        h, jnp.asarray(labels), (ln_w, ln_b, head_w), eps=1e-5
    )
    got = float(lsum) / float(w)
    # reference: the tape-path math on the same arrays
    from accelerate_tpu.models.gpt import _pure_layernorm

    logits = Tensor(_pure_layernorm(h, ln_w, ln_b, 1e-5) @ head_w.T)
    want = float(lm_shift_loss(logits, jnp.asarray(labels), v).data)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_padded_label_parity_between_schedules():
    """UNEVEN -100 padding across microbatches: the fused loss must still be
    the global token mean, not a mean of per-microbatch means (which would
    over-weight heavily-padded microbatches)."""
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 1024, (32, 32)).astype(np.int32)
    labels = ids.copy()
    # ragged padding: rows get anywhere from 0 to 24 trailing -100s
    for i in range(32):
        pad = int(rng.integers(0, 25))
        if pad:
            labels[i, -pad:] = -100

    def run(schedule):
        Accelerator._reset_state()
        nn.manual_seed(0)
        acc = Accelerator(
            parallelism_config=ParallelismConfig(pp_size=2),
            pp_plugin=PipelineParallelPlugin(
                pp_size=2, num_microbatches=8, schedule=schedule
            ),
            mixed_precision="no",
        )
        model = PipelinedGPTLMHeadModel(GPTConfig.tiny(), num_microbatches=8)
        opt = optim.SGD(model.parameters(), lr=0.1)
        model, opt = acc.prepare(model, opt)

        def step_fn(x, y):
            opt.zero_grad()
            out = model(x, labels=y)
            acc.backward(out["loss"])
            opt.step()
            return out["loss"]

        step = acc.compile_step(step_fn)
        x = batch_to_global_array(jnp.asarray(ids), mesh=acc.mesh)
        y = batch_to_global_array(jnp.asarray(labels), mesh=acc.mesh)
        losses = [float(step(x, y)) for _ in range(2)]
        return losses, {n: np.asarray(p.data) for n, p in model.named_parameters()}

    l_g, p_g = run("gpipe")
    l_f, p_f = run("1f1b")
    np.testing.assert_allclose(l_f, l_g, rtol=2e-5, atol=2e-5)
    for name in p_g:
        np.testing.assert_allclose(
            p_f[name], p_g[name], rtol=3e-4, atol=3e-5, err_msg=name
        )


def test_1f1b_loss_decreases():
    losses, _ = _train("1f1b", steps=4)
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_interleaved_grad_parity_with_gpipe_at_v2(compiled_in_this_process):
    """ISSUE 15 acceptance: the interleaved schedule (V=2, each device
    hosting two non-contiguous layer spans) trains identically to GPipe —
    loss trajectory AND updated parameters agree on a 4-layer trunk."""
    l_g, p_g = _train("gpipe", n_layer=4)
    l_i, p_i = _train("interleaved", n_layer=4, virtual=2)
    np.testing.assert_allclose(l_i, l_g, rtol=2e-5, atol=2e-5)
    for name in p_g:
        np.testing.assert_allclose(
            p_i[name], p_g[name], rtol=3e-4, atol=3e-5, err_msg=name
        )


def test_interleaved_matches_fused_1f1b():
    """Same seed/data: interleaving is a schedule/layout change, not a
    numerics change — V=2 must track the fused 1F1B trajectory."""
    l_f, p_f = _train("1f1b", n_layer=4)
    l_i, p_i = _train("interleaved", n_layer=4, virtual=2)
    np.testing.assert_allclose(l_i, l_f, rtol=2e-5, atol=2e-5)
    for name in p_f:
        np.testing.assert_allclose(
            p_i[name], p_f[name], rtol=3e-4, atol=3e-5, err_msg=name
        )


def test_committed_layout_matches_gather_reference():
    """ISSUE 17 acceptance: the prepare-time committed layout (zero
    permutation bytes per step) trains bitwise-identically to the legacy
    in-program gather layout — the permutation moved, the math didn't."""
    l_c, p_c = _train("interleaved", n_layer=4, virtual=2)
    l_g, p_g = _train("interleaved", n_layer=4, virtual=2, layout="gather")
    np.testing.assert_array_equal(np.asarray(l_c), np.asarray(l_g))
    for name in p_g:
        np.testing.assert_array_equal(p_c[name], p_g[name], err_msg=name)


# ---------------------------------------------------------------------------
# cross-layout checkpoints + fleet resize (ISSUE 17 layout contract)
# ---------------------------------------------------------------------------
def _ckpt_run(layout, mp="no", schedule="interleaved", virtual=2):
    """An interleaved pp=2, V=2 AdamW run for checkpoint-matrix tests
    (``schedule="1f1b", virtual=0`` gives the plain-layout V=1 twin)."""
    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(
        parallelism_config=ParallelismConfig(pp_size=2),
        pp_plugin=PipelineParallelPlugin(
            pp_size=2, num_microbatches=8, schedule=schedule,
            virtual_stages=virtual, layout=layout,
        ),
        mixed_precision=mp,
    )
    import dataclasses as _dc

    cfg = _dc.replace(GPTConfig.tiny(), n_layer=4)
    model = PipelinedGPTLMHeadModel(cfg, num_microbatches=8)
    opt = optim.AdamW(model.parameters(), lr=1e-3)
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
            np.random.default_rng(0).integers(0, 1024, (32, 32)), jnp.int32
        ),
        mesh=acc.mesh,
    )
    return acc, model, opt, step, ids


def test_committed_layout_lowers_without_the_layer_gather():
    """The same fact where it is structural (docs/parallel_plan.md): the
    committed layout's lowering holds no gather op and no layer-order index
    vector, and the gather layout's — the contrast — holds both."""
    from accelerate_tpu.native.kernels.inspect import check_pipeline_layout

    facts = check_pipeline_layout()
    assert facts["committed_gather_ops"] == 0 and facts["committed_order_vectors"] == 0
    assert facts["gather_gather_ops"] > 0 and facts["gather_order_vectors"] > 0


def _plain_opt_state(acc, model, opt):
    """Moments (+ masters when present) for STACKED params, viewed in plain
    layer order — the cross-layout bitwise-comparison unit.  Leaf→param
    ownership follows ``Optimizer._map_per_param_state``'s SequenceKey +
    exact-shape rule."""
    import jax

    stage = acc.plan.stage
    inner = getattr(opt, "optimizer", opt)
    stacked_ids = {id(p) for _, p in acc._stacked_layer_params(model)}
    committed = {
        id(p)
        for _, p in acc._stacked_layer_params(model)
        if getattr(p, "_layer_layout_committed", False)
    }
    shapes = [tuple(p.shape) for p in inner.param_list]

    def view(leaf, p):
        a = np.asarray(leaf)
        if id(p) in committed and a.ndim:
            a = a[np.asarray(stage.inverse_layer_order(a.shape[0]))]
        return a

    out = {}
    for i, p in enumerate(inner.param_list):
        if id(p) in stacked_ids and inner.master_params[i] is not None:
            out[f"master.{i}"] = view(inner.master_params[i], p)
    for path, leaf in jax.tree_util.tree_flatten_with_path(inner.opt_state)[0]:
        idx = next(
            (k.idx for k in reversed(path)
             if isinstance(k, jax.tree_util.SequenceKey)),
            None,
        )
        if (
            idx is not None
            and idx < len(shapes)
            and hasattr(leaf, "shape")
            and tuple(leaf.shape) == shapes[idx]
            and id(inner.param_list[idx]) in stacked_ids
        ):
            out[f"state.{idx}.{jax.tree_util.keystr(path)}"] = view(
                leaf, inner.param_list[idx]
            )
    return out


@pytest.mark.parametrize(
    "save_kw,load_kw",
    [
        ({"layout": None}, {"layout": "gather"}),
        ({"layout": "gather"}, {"layout": None}),
        pytest.param(
            {"layout": None},
            {"layout": None, "schedule": "1f1b", "virtual": 0},
            marks=pytest.mark.slow,
        ),
        pytest.param(
            {"layout": None, "schedule": "1f1b", "virtual": 0},
            {"layout": None},
            marks=pytest.mark.slow,
        ),
    ],
    ids=[
        "committed_to_gather",
        "gather_to_committed",
        "committed_to_plain_v1",
        "plain_v1_to_committed",
    ],
)
def test_checkpoint_cross_layout_matrix(tmp_path, save_kw, load_kw):
    """Checkpoints written under one stacked-layer layout restore into a
    run living under ANOTHER — the restore transposition covers params,
    fp32 masters, and moments bitwise, and the resumed trajectory tracks
    the uninterrupted one.  Gather- and V=1-layout checkpoints carry no
    ``layer_layout`` meta (byte-identical to pre-layout-era ones), so the
    *→committed legs double as the backward-compat proof."""
    acc, model, opt, step, ids = _ckpt_run(**save_kw)
    for _ in range(2):
        float(step(ids))
    out = str(tmp_path / "ckpt")
    acc.save_state(out)
    saved_params = _plain_params(acc, model)
    saved_opt = _plain_opt_state(acc, model, opt)
    cont = [float(step(ids)) for _ in range(2)]

    acc2, model2, opt2, step2, ids2 = _ckpt_run(**load_kw)
    acc2.load_state(out)
    # params + optimizer state bitwise in the plain view after transposition
    got_params = _plain_params(acc2, model2)
    for name in saved_params:
        np.testing.assert_array_equal(
            got_params[name], saved_params[name], err_msg=name
        )
    got_opt = _plain_opt_state(acc2, model2, opt2)
    assert set(got_opt) == set(saved_opt)
    for name in saved_opt:
        np.testing.assert_array_equal(got_opt[name], saved_opt[name], err_msg=name)
    resumed = [float(step2(ids2)) for _ in range(2)]
    np.testing.assert_allclose(resumed, cont, rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_checkpoint_masters_transpose_bitwise(tmp_path):
    """bf16 params give the optimizer real fp32 masters; a committed-layout
    save restored into a gather-layout run must hand back the SAME master
    bytes in the plain view."""
    acc, model, opt, step, ids = _ckpt_run(None, mp="bf16")
    float(step(ids))
    out = str(tmp_path / "ckpt")
    acc.save_state(out)
    saved = _plain_opt_state(acc, model, opt)
    masters = [k for k in saved if k.startswith("master.")]
    assert masters, "bf16 run grew no fp32 masters for stacked params"

    acc2, model2, opt2, step2, ids2 = _ckpt_run("gather", mp="bf16")
    acc2.load_state(out)
    got = _plain_opt_state(acc2, model2, opt2)
    for name in masters:
        np.testing.assert_array_equal(got[name], saved[name], err_msg=name)


@pytest.mark.slow
def test_fleet_resize_preserves_committed_layout(tmp_path):
    """A dp resize (drain → re-mesh → reshard restore) must keep the
    prepare-time layout of record: the survivors' stacked params stay
    COMMITTED (markers intact, plan still says so), their plain view is
    bitwise the pre-resize one, and training continues."""
    from accelerate_tpu import FleetKwargs

    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(
        parallelism_config=ParallelismConfig(pp_size=2),
        pp_plugin=PipelineParallelPlugin(
            pp_size=2, num_microbatches=8, schedule="interleaved",
            virtual_stages=2,
        ),
        mixed_precision="no",
        kwargs_handlers=[FleetKwargs(enabled=True)],
    )
    import dataclasses as _dc

    cfg = _dc.replace(GPTConfig.tiny(), n_layer=4)
    model = PipelinedGPTLMHeadModel(cfg, num_microbatches=8)
    opt = optim.SGD(model.parameters(), lr=0.1)
    model, opt = acc.prepare(model, opt)
    dp = acc.plan.dp
    if dp < 2:
        pytest.skip("needs dp >= 2 beside pp=2")

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    ids = batch_to_global_array(
        jnp.asarray(
            np.random.default_rng(0).integers(0, 1024, (32, 32)), jnp.int32
        ),
        mesh=acc.mesh,
    )
    float(step(ids))
    before = _plain_params(acc, model)

    acc.fleet.resize(acc, target_dp=dp // 2, output_dir=str(tmp_path / "drain"))
    assert acc.plan.pp == 2 and acc.plan.dp == dp // 2
    assert acc.plan.layer_layout == "committed"
    stacked = acc._stacked_layer_params(model)
    assert stacked and all(
        getattr(p, "_layer_layout_committed", False) for _, p in stacked
    )
    after = _plain_params(acc, model)
    for name in before:
        np.testing.assert_array_equal(after[name], before[name], err_msg=name)
    ids2 = batch_to_global_array(
        jnp.asarray(
            np.random.default_rng(0).integers(0, 1024, (32, 32)), jnp.int32
        ),
        mesh=acc.mesh,
    )
    assert np.isfinite(float(step(ids2)))


def test_interleaved_rejects_indivisible_shapes():
    """Bad geometry fails loudly at construction (plan resolution), not
    mid-first-step: M not divisible by S, layers not divisible by S·V."""
    with pytest.raises(ValueError, match="divisible"):
        _train("interleaved", microbatches=3, n_layer=4, virtual=2)
    # layers 2 vs S·V = 4: the layer-order derivation refuses
    from accelerate_tpu.parallel.plan import StagePlan

    with pytest.raises(ValueError, match="not divisible"):
        StagePlan(
            num_stages=2, virtual=2, num_microbatches=8,
            schedule="interleaved",
        ).layer_order(2)


def test_1f1b_rejects_sequence_parallel():
    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(
        parallelism_config=ParallelismConfig(pp_size=2, sp_size=2),
        pp_plugin=PipelineParallelPlugin(pp_size=2, schedule="1f1b"),
    )
    model = PipelinedGPTLMHeadModel(GPTConfig.tiny(), num_microbatches=2)
    model, = (acc.prepare(model),)
    ids = batch_to_global_array(
        jnp.zeros((8, 32), jnp.int32), mesh=acc.mesh
    )
    with pytest.raises(NotImplementedError, match="sequence parallelism"):
        model(ids, labels=ids)


def test_bad_schedule_name_rejected():
    with pytest.raises(ValueError, match="gpipe"):
        PipelineParallelPlugin(pp_size=2, schedule="zigzag")
    # interleaving is a 1F1B property: gpipe can't take a virtual factor,
    # and 'interleaved' with V=1 is a contradiction
    with pytest.raises(ValueError, match="gpipe"):
        PipelineParallelPlugin(pp_size=2, schedule="gpipe", virtual_stages=2)
    with pytest.raises(ValueError, match="virtual_stages"):
        PipelineParallelPlugin(pp_size=2, schedule="interleaved", virtual_stages=1)
