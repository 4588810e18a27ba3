"""Lowering-IR inspection harness: prove the fusion actually happened
(docs/kernels.md §IR contract).

A kernel that silently de-fuses — an all-gather the compiler re-separated
from its consuming matmuls — would still pass every numerics test, because the reference and the
kernel compute the same values by design.  The only place the fusion is
visible is the IR the program commits to, so each check here lowers the
kernel path (``jax.jit(...).lower().compiler_ir()``) and asserts the
structural fact that IS the optimization:

* ``check_collective_matmul`` — NO ``all_gather`` op anywhere in the
  kernel path's IR; the transport is chunked ``collective_permute`` hops
  with one partial dot per chunk (and the Pallas partial-dot kernel is in
  the jaxpr).  The reference contrast (a plain dot on the dp-committed
  weight) partitions to exactly the all-gather-then-dot the kernel exists
  to remove.
* ``check_quantize_rs`` — the narrow wire dtype (``i8`` / ``f8E4M3FN``)
  appears in the kernel path's IR (the payload crosses narrow) and the
  rounding op lives INSIDE the kernel region (the grid loop the
  interpreter lowers to), not as a free-floating top-level op between HBM
  round-trips.

Every check returns the dict of facts it asserted.  (The decode program's
paged attention is held against the chip's compiler instead:
tests/test_tpu_compile.py asserts one Mosaic kernel a layer body and no
tensor of the gathered span's size in the compiled program.)
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from ...parallel.mesh import make_mesh

__all__ = [
    "stablehlo_text",
    "jaxpr_text",
    "check_collective_matmul",
    "check_quantize_rs",
    "check_pipeline_layout",
]

_ALL_GATHER_RE = re.compile(r"all[_-]gather", re.IGNORECASE)


def stablehlo_text(fn, *args, in_shardings=None) -> str:
    """The IR the program commits to at trace level —
    ``lower().compiler_ir()`` per the harness contract."""
    jitted = jax.jit(fn) if in_shardings is None else jax.jit(
        fn, in_shardings=in_shardings
    )
    return str(jitted.lower(*args).compiler_ir(dialect="stablehlo"))


def compiled_text(fn, *args, in_shardings=None) -> str:
    """Post-partitioning HLO (``lower().compile().as_text()``): where
    GSPMD's inserted collectives become visible — used for the reference
    contrasts, whose all-gather only exists after partitioning."""
    jitted = jax.jit(fn) if in_shardings is None else jax.jit(
        fn, in_shardings=in_shardings
    )
    return jitted.lower(*args).compile().as_text()


def jaxpr_text(fn, *args) -> str:
    return str(jax.make_jaxpr(fn)(*args))


def check_collective_matmul(mesh=None, *, m: int = 8, k_chunk: int = 8,
                            n_out: int = 16, interpret: bool = True) -> dict:
    """No unfused all-gather-then-dot: the kernel path's IR carries zero
    ``all_gather`` ops, ``dp`` chunked ``collective_permute`` hops feeding
    per-chunk dots, and the Pallas partial-dot kernel."""
    from .collective_matmul import collective_matmul, reference_collective_matmul

    if mesh is None:
        mesh = make_mesh({"dp": len(jax.devices())}, axis_order=("dp",))
    n = mesh.shape["dp"]
    P = jax.sharding.PartitionSpec
    x = jnp.ones((m, k_chunk * n), jnp.float32)
    w = jnp.ones((k_chunk * n, n_out), jnp.float32)

    def fused(x, w):
        return collective_matmul(x, w, mesh=mesh, interpret=interpret)

    text = stablehlo_text(fused, x, w)
    facts = {
        "dp": n,
        "fused_has_all_gather": bool(_ALL_GATHER_RE.search(text)),
        "fused_permute_hops": text.count("collective_permute"),
        "fused_partial_dots": text.count("stablehlo.dot_general"),
        "pallas_partial_dot_in_jaxpr": "pallas_call" in jaxpr_text(fused, x, w),
    }
    assert not facts["fused_has_all_gather"], (
        "collective-matmul lowering still contains an all-gather — the "
        "monolithic gather the kernel exists to remove"
    )
    if n > 1:
        assert facts["fused_permute_hops"] >= 1, "no chunked transport hops"
        assert facts["fused_partial_dots"] >= n, (
            f"expected >= {n} per-chunk partial dots, found "
            f"{facts['fused_partial_dots']}"
        )
    assert facts["pallas_partial_dot_in_jaxpr"]
    # contrast: the reference dot on a dp-committed weight partitions into
    # all-gather-then-dot (fail-soft: some backends refuse to partition)
    try:
        ref_text = compiled_text(
            reference_collective_matmul, x, w,
            in_shardings=(
                jax.sharding.NamedSharding(mesh, P()),
                jax.sharding.NamedSharding(mesh, P("dp", None)),
            ),
        )
        facts["reference_has_all_gather"] = bool(_ALL_GATHER_RE.search(ref_text))
    except Exception as exc:  # pragma: no cover - backend-dependent
        facts["reference_has_all_gather"] = f"unavailable: {type(exc).__name__}"
    return facts


def check_quantize_rs(*, shape=(32, 16), wire_dtype=jnp.int8,
                      interpret: bool = True) -> dict:
    """Scale+round fused into the kernel region, narrow payload in the IR:
    the wire dtype appears (the boundary is crossed narrow) and the
    rounding op sits inside the kernel's lowered region, not between
    top-level HBM round-trips."""
    from .quantize_rs import fused_quantize_dequantize

    x = jnp.ones(shape, jnp.float32)

    def fused(x):
        return fused_quantize_dequantize(x, 0, wire_dtype, interpret=interpret)

    text = stablehlo_text(fused, x)
    narrow = "i8" if jnp.dtype(wire_dtype) == jnp.int8 else "f8E4M3"
    region_at = text.find("stablehlo.while")  # the kernel region's lowering
    round_at = text.find("round_nearest")
    facts = {
        "narrow_payload_in_ir": f"x{narrow}>" in text or f"x{narrow} " in text,
        "kernel_region_present": region_at >= 0,
        "round_inside_kernel_region": round_at > region_at >= 0,
        "pallas_call_in_jaxpr": "pallas_call" in jaxpr_text(fused, x),
    }
    assert facts["narrow_payload_in_ir"], (
        "quantize-rs lowering shows no narrow payload — the wire widened "
        "before the boundary"
    )
    assert facts["kernel_region_present"] and facts["pallas_call_in_jaxpr"]
    assert facts["round_inside_kernel_region"], (
        "rounding lowered outside the kernel region — the scale/round "
        "fusion did not happen"
    )
    return facts


def check_pipeline_layout(mesh=None, *, num_stages: int = 2, virtual: int = 3,
                          num_layers: int = 6, dim: int = 8,
                          microbatches: int = 4) -> dict:
    """Zero permutation bytes in the committed interleaved 1F1B step
    (ISSUE 17 acceptance): the committed-layout lowering contains NO
    gather op and NO ``num_layers``-long index vector anywhere, while the
    legacy ``gather`` layout's lowering carries both — the in-program
    ``jnp.take`` of the layer order (and its inverse on the gradients)
    that the prepare-time commit removed."""
    from ...parallel.pipeline import apply_layer_order, pipeline_train_1f1b
    from ...parallel.plan import _layer_orders

    if mesh is None:
        mesh = make_mesh(
            {"pp": num_stages}, devices=jax.devices()[:num_stages],
            axis_order=("pp",),
        )
    S, V, L = num_stages, virtual, num_layers
    ks = jax.random.split(jax.random.key(0), L)
    plain = {
        "w": jnp.stack([jax.random.normal(k, (dim, dim)) * 0.5 for k in ks]),
        "b": jnp.zeros((L, dim)),
    }
    committed = apply_layer_order(plain, _layer_orders(S, V, L)[0])
    batch = microbatches * 2
    x = jax.random.normal(jax.random.key(1), (batch, dim))
    labels = jax.random.normal(jax.random.key(2), (batch, dim))
    extra = {"head": jnp.eye(dim)}

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def loss_fn(out, lbl, e):
        err = (out @ e["head"] - lbl) ** 2
        return err.sum(), jnp.float32(err.size)

    def lowered(layout, params):
        def f(p, x_, l_, e_):
            return pipeline_train_1f1b(
                stage_fn, p, x_, l_, e_, loss_fn, microbatches,
                mesh=mesh, virtual=V, layout=layout,
            )

        return stablehlo_text(f, params, x, labels, extra)

    gather_op = re.compile(r"stablehlo\.(?:dynamic_)?gather")
    idx_vec = f"tensor<{L}xi32>"  # the traced layer-order index vector
    committed_text = lowered("committed", committed)
    gather_text = lowered("gather", plain)
    facts = {
        "geometry": {"num_stages": S, "virtual": V, "num_layers": L},
        "committed_gather_ops": len(gather_op.findall(committed_text)),
        "committed_order_vectors": committed_text.count(idx_vec),
        "gather_gather_ops": len(gather_op.findall(gather_text)),
        "gather_order_vectors": gather_text.count(idx_vec),
    }
    assert facts["committed_gather_ops"] == 0, (
        "committed-layout 1F1B lowering still contains a gather — the "
        "stacked-layer permutation the prepare-time commit exists to remove"
    )
    assert facts["committed_order_vectors"] == 0, (
        "committed-layout lowering carries a layer-order index vector"
    )
    assert facts["gather_gather_ops"] > 0 and facts["gather_order_vectors"] > 0, (
        "gather-layout reference no longer traces the in-program permutation "
        "— the inspection contrast lost its meaning; update the harness"
    )
    return facts
