"""The plain reference against the program's own model at a tiny size, in
float32 on the CPU: same weights, same ids, same logits, loss and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import helpers
from benchmark import cells

jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return cells.resolve("tiny.train", helpers.fixture_repo(str(tmp_path_factory.mktemp("ref"))))


def test_weights_are_a_pure_function_of_the_seed(cell):
    ref = cell.reference
    a = ref.init_params(cell.config, 2**31 + 99)
    b = ref.init_params(cell.config, 2**31 + 99)
    c = ref.init_params(cell.config, 2**31 + 100)
    d = ref.init_params(cell.config, 2**32 + 2**31 + 99)  # differs in the high word only
    eq = lambda x, y: all(  # noqa: E731
        np.array_equal(p, q) for p, q in zip(jax.tree_util.tree_leaves(x), jax.tree_util.tree_leaves(y))
    )
    assert eq(a, b) and not eq(a, c) and not eq(a, d)
    # bfloat16 values whatever the carrier
    w = np.asarray(a["blocks"]["fc_w"])
    assert np.array_equal(w, np.asarray(jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)))
    assert list(ref.leaf_norms(a))[:4] == list(ref.GLOBAL_KEYS)


def test_reference_agrees_with_the_program_model(cell):
    import accelerate_tpu.nn as nn

    ref, family, cfg = cell.reference, cell.family, cell.config
    params = ref.init_params(cfg, 7)
    model = family.build_model(cfg, params)
    names = [family.canonical_name(n) for n, _ in family.named_parameters(model)]
    split = [n for name, p in zip(names, family.named_parameters(model))
             for n, _ in ref.split_leaf(name, p[1].data)]
    assert sorted(split) == sorted(ref.leaf_norms(params))
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 64), dtype=np.int32)
    out = model(ids, labels=ids)
    want = ref.logits(params, jnp.asarray(ids), cfg["n_head"])
    np.testing.assert_allclose(np.asarray(out["logits"].data), np.asarray(want), atol=2e-5)
    total, count = ref.nll_sum(params, jnp.asarray(ids), cfg["n_head"])
    assert float(out["loss"].data) == pytest.approx(float(total) / count, rel=1e-5)
    out["loss"].backward()
    loss, grad = ref.loss_and_grad(params, jnp.asarray(ids), cfg["n_head"], block_rows=1)
    assert float(loss) == pytest.approx(float(out["loss"].data), rel=1e-5)
    for name, p in family.named_parameters(model):
        g = np.asarray(family.leaf_of(grad, family.canonical_name(name)))
        np.testing.assert_allclose(np.asarray(p.grad), g, atol=2e-6, rtol=1e-4, err_msg=name)
    with nn.no_grad():
        pass


def test_adamw_is_optax_adamw(cell):
    import optax

    ref, cfg, o = cell.reference, cell.config, cell.mix["optimizer"]
    params = ref.init_params(cfg, 11)
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (3, 2, 32), dtype=np.int32)
    got = ref.train_steps(jax.tree_util.tree_map(jnp.copy, params), list(ids), cfg["n_head"], o, block_rows=2)
    tx = optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
    state, p = tx.init(params), params
    for batch in ids:
        _, g = ref.loss_and_grad(p, jnp.asarray(batch), cfg["n_head"], block_rows=2)
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
    want = ref.leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, params))
    for k, v in want.items():
        if k.endswith(".k_b"):
            continue  # no gradient under softmax: these move by round-off alone
        assert got["update_norms"][k] == pytest.approx(v, rel=2e-4), k
    assert len(got["losses"]) == 3 and set(got["grad_norms"]) == set(want)
    assert "h.0.k_b" in want and "h.0.qkv_b" not in want


def test_lower_precisions_differ_from_the_reference_in_order(cell):
    ref, cfg = cell.reference, cell.config
    params = ref.init_params(cfg, 5)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, cfg["vocab_size"], (2, 64), dtype=np.int32))
    exact = ref.logits(params, ids, cfg["n_head"], "float32")
    err = {p: float(jnp.max(jnp.abs(ref.logits(params, ids, cfg["n_head"], p) - exact)))
           for p in ("bfloat16", "fp8")}
    assert 0 < err["bfloat16"] < err["fp8"]
    loss8, grad8 = ref.loss_and_grad(params, ids, cfg["n_head"], "fp8", block_rows=2)
    assert np.isfinite(float(loss8)) and all(
        bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grad8)
    )
    gaps = ref.served_token_gaps(params, np.asarray(ids[0]), 40, cfg["n_head"], 128)
    assert gaps.shape == (24,) and (gaps >= 0).all()
