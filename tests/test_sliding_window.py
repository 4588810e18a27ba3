"""Sliding-window (Mistral-style) attention: flash-kernel parity with the
reference band mask, gradients, tile skipping, and the Llama family knob.
Kernels run in interpret mode on CPU (same block schedule as TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import accelerate_tpu.ops.flash_attention as fa
from accelerate_tpu.ops.attention import sdpa_reference


def _rand_qkv(b=1, h=2, s=256, d=64, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(kq, (b, h, s, d), jnp.float32),
        jax.random.normal(kk, (b, h, s, d), jnp.float32),
        jax.random.normal(kv, (b, h, s, d), jnp.float32),
    )


def test_reference_band_mask_semantics():
    """Row i of the reference band softmax spans exactly (i-w, i]."""
    s, w = 8, 3
    q = jnp.zeros((1, 1, s, 4))
    k = jnp.zeros((1, 1, s, 4))
    v = jnp.eye(s)[None, None, :, :4]  # value j one-hot → probs readable
    out = sdpa_reference(q, k, v, is_causal=True, window=w)
    probs_row = np.asarray(out[0, 0])  # uniform over the band
    for i in range(s):
        lo = max(0, i - w + 1)
        width = i - lo + 1
        expect = np.zeros(4)
        for j in range(lo, min(i + 1, 4)):
            expect[j] = 1.0 / width
        np.testing.assert_allclose(probs_row[i][:4], expect[:4], atol=1e-6)


@pytest.mark.parametrize("window", [128, 256, 384])
def test_forward_matches_reference(window):
    q, k, v = _rand_qkv(s=512)
    out = fa.flash_attention(q, k, v, True, None, window)
    ref = sdpa_reference(q, k, v, is_causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_window_not_multiple_of_block():
    """Bands that cut through tiles (not block-aligned) still mask exactly."""
    q, k, v = _rand_qkv(s=256)
    out = fa.flash_attention(q, k, v, True, None, 200)
    ref = sdpa_reference(q, k, v, is_causal=True, window=200)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [64, 200, 256])
def test_narrowed_grid_multi_tile_parity(window):
    """128-tile grid at seq 512 → the narrowed k-grid path (window_tiles>0)
    runs with real clamped-duplicate visits; parity must hold exactly."""
    q, k, v = _rand_qkv(s=512)
    out = fa._flash_forward(
        q, k, v, q.shape[-1] ** -0.5, True, block_q=128, block_k=128,
        window=window,
    )
    ref = sdpa_reference(q, k, v, is_causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_cross_length_windowed_matches_reference():
    """sq != sk must NOT take the narrowed grid (clamped tiles would be
    mislabeled — review catch, reproduced): full-grid fallback stays exact.

    The kernel's cross-length causal convention is START-aligned global
    positions (q_pos = i, k_pos = j — the ring-hop contract), so compare
    against a start-aligned band reference, with window large enough that
    every q row keeps at least one visible key.
    """
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (1, 2, 512, 64), jnp.float32)
    k = jax.random.normal(kk, (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(kv, (1, 2, 256, 64), jnp.float32)
    window = 384  # > 511 - 255: no fully-masked q rows
    out = fa._flash_forward(
        q, k, v, 64 ** -0.5, True, block_q=128, block_k=128, window=window
    )
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * (
        64 ** -0.5
    )
    i = jnp.arange(512)[:, None]
    j = jnp.arange(256)[None, :]
    keep = (i >= j) & (i - j < window)
    logits = jnp.where(keep[None, None], logits, -0.7 * float(jnp.finfo(jnp.float32).max))
    ref = jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1).astype(v.dtype), v
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_narrowed_grid_only_without_offsets():
    """Ring hops (traced offsets) must keep the full k-grid — offsets are
    invisible to the static index map."""
    q, k, v = _rand_qkv(s=256)
    # static zero offsets → narrowed; same call with traced offsets must
    # still be correct (falls back to full grid + predicate)
    out = fa._flash_forward(
        q, k, v, q.shape[-1] ** -0.5, True, block_q=128, block_k=128,
        window=128, q_offset=jnp.asarray(0), k_offset=jnp.asarray(0),
    )
    ref = sdpa_reference(q, k, v, is_causal=True, window=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_decode_matches_forward_for_windowed_config():
    """Windowed configs: cached decode logits == training forward logits for
    the same prefix (the drift the review caught)."""
    import accelerate_tpu.nn as nn
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    nn.manual_seed(0)
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=64, sliding_window=16,
    )
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(1).integers(0, 256, (1, 48)).astype(np.int32)
    fwd_logits = np.asarray(model(nn.Tensor(jnp.asarray(ids)))["logits"].data)

    from accelerate_tpu.models.generation import generate

    # greedy decode's first token == argmax of the training-forward logits
    # at the last prefix position; with window 16 << 48 any full-causal
    # prefill would disagree (verified: removing the decode window breaks it)
    out = np.asarray(generate(model, jnp.asarray(ids), max_new_tokens=1))
    assert out.shape[1] == 49
    assert out[0, -1] == int(fwd_logits[0, -1].argmax())


def test_backward_matches_reference():
    q, k, v = _rand_qkv(s=512)
    w = 256

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, True, None, w)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = sdpa_reference(q, k, v, is_causal=True, window=w)
        return jnp.sum(o * jnp.cos(o))

    gq, gk, gv = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("window", [64, 200, 384])
def test_backward_narrowed_grid_parity(window):
    """Multi-tile narrowed dq/dkv kernel pair (block 128 at seq 512) vs the
    band reference — covers clamp-duplicate visits on both grid walks."""
    q, k, v = _rand_qkv(s=512)
    scale = 64 ** -0.5

    # route the backward through _flash_backward with small blocks
    out, lse = fa._flash_forward(
        q, k, v, scale, True, block_q=128, block_k=128,
        window=window, return_lse=True,
    )
    g = jnp.cos(out) - out * jnp.sin(out)  # d/do of sum(o*cos(o))
    gq, gk, gv = fa._flash_backward(
        q, k, v, out, lse[..., 0], g, scale, True,
        block_q=128, block_k=128, window=window,
    )

    def loss_ref(q, k, v):
        o = sdpa_reference(q, k, v, is_causal=True, window=window)
        return jnp.sum(o * jnp.cos(o))

    rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("mode", ["ring", "all_to_all"])
@pytest.mark.parametrize("window", [8, 20])
def test_sequence_parallel_window_parity(mode, window):
    """Windowed SP attention (ring hop-skipping / Ulysses local band) on the
    8-device CPU mesh matches the single-device band reference, values and
    grads. Window 8 == chunk (out-of-band hops actually skip); 20 cuts
    through chunk boundaries."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu.ops.ring_attention import sequence_parallel_attention
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import ParallelismConfig

    AcceleratorState._reset_state()
    mesh = AcceleratorState(
        parallelism_config=ParallelismConfig(sp_size=4, dp_size=2)
    ).mesh
    b, h, s, d = 2, 4, 32, 8  # chunk = 8 per sp device
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    expected = sdpa_reference(q, k, v, is_causal=True, window=window)

    def place(x):
        return jax.device_put(x, NamedSharding(mesh, P("dp", None, "sp", None)))

    out = jax.jit(
        lambda a, b_, c: sequence_parallel_attention(
            a, b_, c, mesh=mesh, is_causal=True, mode=mode, window=window
        )
    )(place(q), place(k), place(v))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)

    def sp_loss(q_, k_, v_):
        return sequence_parallel_attention(
            q_, k_, v_, mesh=mesh, is_causal=True, mode=mode, window=window
        ).sum()

    def ref_loss(q_, k_, v_):
        return sdpa_reference(q_, k_, v_, is_causal=True, window=window).sum()

    g_sp = jax.grad(sp_loss, argnums=(0, 1, 2))(place(q), place(k), place(v))
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, ge in zip(g_sp, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(ge),
                                   rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("window", [64, 200])
def test_ring_flash_hop_windowed_parity(window):
    """The Pallas flash-hop windowed ring path (chunk 128): in-kernel band
    masking with traced offsets, the hop vjp's window threading, and the
    whole-hop band skip — forward and grads vs the band reference."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import accelerate_tpu.ops.ring_attention as ra
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import ParallelismConfig

    AcceleratorState._reset_state()
    mesh = AcceleratorState(parallelism_config=ParallelismConfig(sp_size=2)).mesh
    b, h, s, d = 4, 2, 256, 64  # chunk = 128: MXU-tileable → flash hops
    # (b=4: the remaining mesh devices land on dp, so batch must divide dp)
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    expected = sdpa_reference(q, k, v, is_causal=True, window=window)

    def place(x):
        return jax.device_put(x, NamedSharding(mesh, P("dp", None, "sp", None)))

    import unittest.mock as mock

    with mock.patch.object(ra, "_FORCE_FLASH_HOPS", True):
        out = jax.jit(
            lambda a, b_, c: ra.ring_attention(
                a, b_, c, mesh=mesh, is_causal=True, window=window
            )
        )(place(q), place(k), place(v))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-4, atol=2e-5)

        def ring_loss(q_, k_, v_):
            return ra.ring_attention(
                q_, k_, v_, mesh=mesh, is_causal=True, window=window
            ).sum()

        g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(
            place(q), place(k), place(v)
        )

    def ref_loss(q_, k_, v_):
        return sdpa_reference(q_, k_, v_, is_causal=True, window=window).sum()

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, ge in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(ge),
                                   rtol=5e-4, atol=1e-5)


def test_window_requires_causal():
    q, k, v = _rand_qkv(s=128)
    with pytest.raises(ValueError, match="sliding window"):
        fa.flash_attention(q, k, v, False, None, 64)
    with pytest.raises(ValueError, match="sliding window"):
        sdpa_reference(q, k, v, is_causal=False, window=64)
    # SP entry points validate identically on sp>1 meshes (review finding:
    # the ring silently ignored the window there)
    from accelerate_tpu.ops.ring_attention import ring_attention
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import ParallelismConfig

    AcceleratorState._reset_state()
    mesh = AcceleratorState(parallelism_config=ParallelismConfig(sp_size=4)).mesh
    qs = jnp.zeros((1, 2, 32, 8))
    with pytest.raises(ValueError, match="sliding window"):
        ring_attention(qs, qs, qs, mesh=mesh, is_causal=False, window=8)


def test_mistral_bridge_parity():
    """transformers MistralForCausalLM converts through the bridge and
    matches the HF forward — including the sliding-window band (seq chosen
    longer than the window so the band actually bites)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "MistralForCausalLM"):
        pytest.skip("transformers build lacks Mistral")

    from accelerate_tpu.utils.torch_bridge import convert_torch_module

    torch.manual_seed(0)
    hf = transformers.MistralForCausalLM(
        transformers.MistralConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, sliding_window=8,
            tie_word_embeddings=False,
        )
    ).eval()
    ours = convert_torch_module(hf)
    assert ours.config.sliding_window == 8
    ids = np.random.default_rng(0).integers(0, 512, (2, 32), dtype=np.int64)
    with torch.no_grad():
        want = hf(torch.tensor(ids)).logits.numpy()
    got = np.asarray(ours(jnp.asarray(ids, jnp.int32))["logits"].data)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_mistral_from_pretrained_dispatch(tmp_path):
    """from_pretrained infers the mistral architecture from config.json and
    loads through the Llama family with the window set (review finding: the
    dispatch registration was missing)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "MistralForCausalLM"):
        pytest.skip("transformers build lacks Mistral")

    from accelerate_tpu.utils.hf import from_pretrained

    torch.manual_seed(0)
    hf = transformers.MistralForCausalLM(
        transformers.MistralConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, sliding_window=8,
            tie_word_embeddings=False,
        )
    ).eval()
    hf.save_pretrained(str(tmp_path))
    ours = from_pretrained(str(tmp_path))
    assert ours.config.sliding_window == 8
    ids = np.random.default_rng(2).integers(0, 512, (1, 32), dtype=np.int64)
    with torch.no_grad():
        want = hf(torch.tensor(ids)).logits.numpy()
    got = np.asarray(ours(jnp.asarray(ids, jnp.int32))["logits"].data)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_llama_sliding_window_config():
    """sliding_window changes the model output vs full causal, and matches a
    reference-path run of the same model."""
    import os

    import accelerate_tpu.nn as nn
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg_kw = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=256,
    )
    ids = nn.Tensor(jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (1, 256)), jnp.int32
    ))

    def logits_for(**extra):
        nn.manual_seed(0)
        model = LlamaForCausalLM(LlamaConfig(**cfg_kw, **extra))
        return np.asarray(model(ids)["logits"].data)

    full = logits_for()
    windowed = logits_for(sliding_window=128)
    assert not np.allclose(full, windowed)  # the band actually applies
    # early positions (inside the window) agree; late positions differ
    np.testing.assert_allclose(full[:, :64], windowed[:, :64], atol=1e-4)
    assert not np.allclose(full[:, -1], windowed[:, -1])


def test_window_tiles_formula():
    """The ONE band-geometry formula all three narrowed walks share: covers
    exactly the tiles a band can touch (never under, at most one spare)."""
    for block in (128, 256, 512):
        for window in (1, 127, 128, 129, 200, 511, 512, 513, 1024):
            num_tiles = 4096 // block
            wt = fa._window_tiles(window, block, num_tiles)
            # exact requirement: a q row at tile edge reaches back window-1
            # positions → floor((window + block - 2) / block) + 1 tiles
            needed = min(num_tiles, (window + block - 2) // block + 1)
            assert needed <= wt <= needed + 1, (block, window, wt, needed)
            assert wt <= num_tiles


def test_dispatcher_forced_paths_honor_window(monkeypatch):
    """ACCELERATE_TPU_FLASH=0 (XLA path) and =1 (Pallas path) both apply the
    band — insurance on the sdpa_tpu plumbing either side of the fork."""
    from accelerate_tpu.ops.attention import sdpa_tpu

    q, k, v = _rand_qkv(s=256)
    ref = sdpa_reference(q, k, v, is_causal=True, window=96)
    monkeypatch.setenv("ACCELERATE_TPU_FLASH", "0")
    out_xla = sdpa_tpu(q, k, v, is_causal=True, window=96)
    np.testing.assert_allclose(np.asarray(out_xla), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    monkeypatch.setenv("ACCELERATE_TPU_FLASH", "1")
    out_pallas = sdpa_tpu(q, k, v, is_causal=True, window=96)
    np.testing.assert_allclose(np.asarray(out_pallas), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
