"""Flash-attention kernel parity tests (interpret mode on CPU).

The Pallas kernels are grid-for-grid the programs that run on TPU; interpret
mode executes the same block schedule on CPU so forward/backward parity is CI
coverage, not TPU-only hope.  Reference: the kernels replace the vendored
fused attention the torch world gets from TE/Megatron (SURVEY.md §2.7.3).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import accelerate_tpu.ops.flash_attention as fa
from accelerate_tpu.ops.attention import sdpa_reference


def _rand_qkv(b=1, h=2, s=256, d=64, dtype=jnp.float32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h, s, d), dtype)
    v = jax.random.normal(kv, (b, h, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("is_causal", [False, True])
def test_forward_matches_reference(is_causal):
    q, k, v = _rand_qkv()
    out = fa.flash_attention(q, k, v, is_causal)
    ref = sdpa_reference(q, k, v, is_causal=is_causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("is_causal", [False, True])
def test_backward_matches_reference(is_causal):
    q, k, v = _rand_qkv()

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, is_causal)
        return jnp.sum(o * jnp.cos(o))  # non-trivial cotangent

    def loss_ref(q, k, v):
        o = sdpa_reference(q, k, v, is_causal=is_causal)
        return jnp.sum(o * jnp.cos(o))

    gq, gk, gv = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=2e-4, rtol=2e-4)


def test_backward_never_materializes_s2(monkeypatch):
    """The backward jaxpr must contain no (sq, sk) = O(S²) intermediate."""
    q, k, v = _rand_qkv(b=1, h=1, s=256, d=64)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    s2 = 256 * 256
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            # pallas_call outputs/inputs stay blocked; no full S×S tensor
            assert not (
                len(shape) >= 2 and shape[-1] * shape[-2] >= s2
            ), f"O(S²) intermediate {shape} from {eqn.primitive}"


def test_bf16_forward_close():
    q, k, v = _rand_qkv(dtype=jnp.bfloat16)
    out = fa.flash_attention(q, k, v, True)
    ref = sdpa_reference(q, k, v, is_causal=True)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        atol=3e-2,
        rtol=3e-2,
    )


# ---------------------------------------------------------------------------
# hop-level API (ring attention inner block)
# ---------------------------------------------------------------------------
def _merge_hops(parts):
    """Logsumexp-merge [(out, lse), ...] partial attentions."""
    out, lse = parts[0]
    out = out.astype(jnp.float32)
    for o, l in parts[1:]:
        lse_new = jnp.logaddexp(lse, l)
        out = out * jnp.exp(lse - lse_new)[..., None] + o.astype(jnp.float32) * jnp.exp(
            l - lse_new
        )[..., None]
        lse = lse_new
    return out


def test_hop_decomposition_matches_full_causal():
    """Chunked hops with offsets merge to exactly full causal attention."""
    q, k, v = _rand_qkv(s=256)
    ref = sdpa_reference(q, k, v, is_causal=True)
    half = 128
    q1 = q[:, :, half:]
    parts = [
        fa.flash_attention_hop(q1, k[:, :, :half], v[:, :, :half], half, 0, True, None),
        fa.flash_attention_hop(q1, k[:, :, half:], v[:, :, half:], half, half, True, None),
    ]
    merged = _merge_hops(parts)
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(ref[:, :, half:]), atol=2e-5, rtol=2e-5
    )
    # first chunk attends only to itself (diagonal hop)
    o0, l0 = fa.flash_attention_hop(
        q[:, :, :half], k[:, :, :half], v[:, :, :half], 0, 0, True, None
    )
    np.testing.assert_allclose(
        np.asarray(o0), np.asarray(ref[:, :, :half]), atol=2e-5, rtol=2e-5
    )


def test_hop_gradients_match_reference():
    """Grads through hop merge == grads through monolithic reference,
    including the lse cotangent path (delta_adjust)."""
    q, k, v = _rand_qkv(s=256, h=1)
    half = 128
    q1 = q[:, :, half:]
    k0, k1 = k[:, :, :half], k[:, :, half:]
    v0, v1 = v[:, :, :half], v[:, :, half:]
    d = q.shape[-1]
    w = jnp.arange(d, dtype=jnp.float32)

    def loss_hops(q1, k0, v0, k1, v1):
        parts = [
            fa.flash_attention_hop(q1, k0, v0, half, 0, True, None),
            fa.flash_attention_hop(q1, k1, v1, half, half, True, None),
        ]
        return (_merge_hops(parts) * w).sum()

    def loss_ref(q1, k0, v0, k1, v1):
        kk = jnp.concatenate([k0, k1], axis=2)
        vv = jnp.concatenate([v0, v1], axis=2)
        s = kk.shape[2]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q1, kk) * (d**-0.5)
        qpos = half + jnp.arange(half)[:, None]
        kpos = jnp.arange(s)[None, :]
        scores = jnp.where(qpos >= kpos, scores, -0.7 * np.finfo(np.float32).max)
        p = jax.nn.softmax(scores, axis=-1)
        return ((p @ vv) * w).sum()

    g_hops = jax.grad(loss_hops, argnums=(0, 1, 2, 3, 4))(q1, k0, v0, k1, v1)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(q1, k0, v0, k1, v1)
    for gh, gr in zip(g_hops, g_ref):
        np.testing.assert_allclose(np.asarray(gh), np.asarray(gr), atol=3e-4, rtol=3e-4)


def test_backward_many_k_blocks_parity():
    """dq must accumulate correctly across MANY backward k-blocks.

    Regression guard: accumulating dq into a non-consecutively revisited
    output block reads stale VMEM whenever the k grid exceeds the window —
    correct at 2 k-blocks, silently corrupt at 3+.  Forcing tiny blocks makes
    seq 512 span 4 k-blocks even in interpret mode.
    """
    import numpy as np

    from accelerate_tpu.ops import flash_attention as fa
    from accelerate_tpu.ops.attention import sdpa_reference

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    for causal in (True, False):
        out, lse = fa._flash_forward(
            q, k, v, 64**-0.5, causal, block_q=128, block_k=128, return_lse=True
        )
        ref_grads = jax.grad(
            loss(lambda q, k, v: sdpa_reference(q, k, v, is_causal=causal)),
            argnums=(0, 1, 2),
        )(q, k, v)
        # same cotangent as the ref loss: d(sum o^2)/do = 2*o
        dq2, dk2, dv2 = fa._flash_backward(
            q, k, v, out, lse[..., 0], 2 * out, 64**-0.5, causal,
            block_q=128, block_k=128,
        )
        for got, want in zip((dq2, dk2, dv2), ref_grads):
            err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
            assert err < 5e-3, f"causal={causal}: rel err {err}"
