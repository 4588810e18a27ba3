"""Ask the chip's compiler, without a chip (on-chip-measurement guide §2).

Every Pallas kernel reachable on a TPU is compiled here in Mosaic mode for a
described ``v5e:2x2`` device at GPT-2-small widths — or, where the compiler
refuses it, the refusal is pinned, with the words the arming error carries
(``native/kernels/TPU_REFUSED``; docs/kernels.md has the table).  A compile
that passes is not a chip run; these guard every later PR at no chip time.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load libtpu, and every xdist worker imports this file.
Keep all such tests in THIS file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import accelerate_tpu.ops.flash_attention as flash
from accelerate_tpu.native.kernels import TPU_REFUSED, KernelPolicy
from accelerate_tpu.state import AcceleratorState

BF16 = jnp.bfloat16
GPT2_SMALL = (12, 12, 1024, 64)  # batch, heads, seq, head_dim of the 12×1024 step
LONG_WIDE = (1, 32, 4096, 128)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip (the next one warns and
    # compiles again): keep the cache out of these
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def dp_mesh(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("dp",))


@pytest.fixture(autouse=True, scope="module")
def mosaic():
    """These compiles target a TPU while jax's default backend is the CPU:
    steer the kernels' backend question to the chip's answer (for the whole
    module: the serving programs are compiled once, in module fixtures)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(flash, "_interpret", lambda: False)
        yield


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def pallas_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def flash_loss(q, k, v):
    return flash.flash_attention(q, k, v, is_causal=True).astype(jnp.float32).sum()


# ---------------------------------------------------------------------------
# the main path's kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [GPT2_SMALL, LONG_WIDE], ids=["gpt2-small", "4096x128"])
def test_flash_forward_compiles_for_v5e(one_chip, shape):
    x = sds(shape, BF16, one_chip)
    compiled = (
        jax.jit(lambda q, k, v: flash.flash_attention(q, k, v, is_causal=True))
        .lower(x, x, x)
        .compile()
    )
    assert pallas_calls(compiled) == 1
    assert "flash_fwd" in compiled.as_text()


@pytest.mark.parametrize("shape", [GPT2_SMALL, LONG_WIDE], ids=["gpt2-small", "4096x128"])
def test_flash_fused_backward_compiles_for_v5e(one_chip, shape):
    x = sds(shape, BF16, one_chip)
    compiled = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2))).lower(x, x, x).compile()
    assert pallas_calls(compiled) == 2  # forward (for residuals) + fused backward
    assert "flash_bwd" in compiled.as_text()


def test_ring_attention_hop_kernel_compiles_for_v5e(one_chip):
    """One hop of ring attention at a real chunk: 4096 tokens over sp=4,
    GPT-2-small heads — forward and backward through the offset-aware
    kernels."""
    chunk = sds((3, 12, 1024, 64), BF16, one_chip)
    offset = sds((), jnp.int32, one_chip)

    def hop_loss(q, k, v, q_off, k_off):
        out, lse = flash.flash_attention_hop(q, k, v, q_off, k_off, True, None, 0)
        return out.astype(jnp.float32).sum() + lse.sum()

    compiled = (
        jax.jit(jax.grad(hop_loss, argnums=(0, 1, 2)))
        .lower(chunk, chunk, chunk, offset, offset)
        .compile()
    )
    assert pallas_calls(compiled) == 2


def test_flash_on_a_mesh_needs_shard_map_and_has_it(dp_mesh, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel — only the TPU lowering says
    so, which is why the multi-chip train step had never lowered on a TPU.
    Bare, the kernel is refused; through the package's dispatch
    (``_flash_on_mesh``: shard_map over the state's mesh) it compiles, one
    kernel per device on a quarter of the batch."""
    from accelerate_tpu.ops import attention

    x = sds(GPT2_SMALL, BF16, NamedSharding(dp_mesh, P("dp")))
    with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
        jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2))).lower(x, x, x).compile()

    monkeypatch.setitem(AcceleratorState._shared_state, "mesh", dp_mesh)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)

    def loss(q, k, v):
        return attention.sdpa_tpu(q, k, v, is_causal=True).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile()
    text = compiled.as_text()
    assert pallas_calls(compiled) == 2
    assert "bf16[36,1024,64]" in text  # 3 of 12 batch rows × 12 heads per device


# ---------------------------------------------------------------------------
# the KernelPolicy kernels and the decode program's attention, Mosaic mode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "shape, axis",
    [((768, 3072), 1), ((50304, 768), 0), ((3072,), 0)],
    ids=["mlp-weight", "embedding", "bias"],
)
def test_quantize_rs_kernel_compiles_for_v5e(one_chip, shape, axis):
    """GPT-2-small's leaves: gridded over blocks of the scaled axis, the
    kernel stays under the scoped-VMEM limit (grid-less, the 768×3072 leaf
    asked for 27 MB against 16)."""
    from accelerate_tpu.native.kernels.quantize_rs import (
        fused_quantize_dequantize,
        stochastic_quantize_dequantize,
    )

    x = sds(shape, jnp.float32, one_chip)
    compiled = (
        jax.jit(lambda x: fused_quantize_dequantize(x, axis, jnp.int8, interpret=False))
        .lower(x)
        .compile()
    )
    assert pallas_calls(compiled) == 1
    key = sds((2,), jnp.uint32, one_chip)
    compiled = (
        jax.jit(lambda x, k: stochastic_quantize_dequantize(x, axis, k, interpret=False))
        .lower(x, key)
        .compile()
    )
    assert pallas_calls(compiled) == 1


def refusal_words(name: str) -> str:
    """The compiler's own words quoted in ``TPU_REFUSED[name]``."""
    quoted = re.findall(r"""['"]([^'"]{20,})['"]""", TPU_REFUSED[name])
    assert quoted, TPU_REFUSED[name]
    return quoted[-1].rstrip(".")


def test_quantize_rs_on_a_sharded_array_is_refused(dp_mesh):
    """Why arming ``quantized_rs`` on a TPU raises: inside the captured step
    the kernel sees a dp-sharded gradient."""
    from accelerate_tpu.native.kernels.quantize_rs import fused_quantize_dequantize

    x = sds((768, 3072), jnp.float32, NamedSharding(dp_mesh, P(None, "dp")))
    with pytest.raises(NotImplementedError) as refused:
        jax.jit(
            lambda x: fused_quantize_dequantize(x, 1, jnp.int8, interpret=False)
        ).lower(x).compile()
    assert "cannot be automatically partitioned" in str(refused.value)
    assert "cannot be automatically partitioned" in TPU_REFUSED["quantized_rs"]


@pytest.mark.parametrize(
    "slots, bps, heads, n_kv, d, layers, num_blocks",
    [(16, 64, 25, 25, 64, 48, 513), (64, 48, 32, 2, 128, 3, 3073)],
    ids=["gpt2-xl.serve-steady", "32-on-2-heads-of-128"],
)
def test_paged_attention_compiles_in_mosaic_for_v5e(one_chip, slots, bps, heads, n_kv, d, layers, num_blocks):
    """A scanned plan's decode attention at the XL cell's shapes, 16 slots x
    64 pages of 16 x 1600 (25 heads of 64; the page 1664 lanes wide, 513 blocks
    a layer), and with grouped kv heads, 64 slots x 48 pages of 16 x 256 (32
    query heads on 2 kv heads of 128: a Llama-geometry family's shape).  One
    Mosaic kernel within the VMEM a kernel gets by default (the compiler
    refuses one that is not), the pools left in HBM: beside it only the spread
    query and the output before each head takes its own lanes."""
    from accelerate_tpu.native.kernels import paged_attention as kernel
    from accelerate_tpu.serving.kv_blocks import page_lanes

    block, lanes = 16, page_lanes(n_kv, d)
    rows = sds((layers * num_blocks, block, lanes), BF16, one_chip)
    compiled = jax.jit(
        lambda q, kp, vp, t, p, first: kernel.paged_attention(
            q, kp, vp, t, p, first, None, n_kv=n_kv
        )
    ).lower(
        sds((slots, heads, d), BF16, one_chip), rows, rows,
        sds((slots, bps), jnp.int32, one_chip), sds((slots,), jnp.int32, one_chip),
        sds((), jnp.int32, one_chip),
    ).compile()
    assert pallas_calls(compiled) == 1
    assert "paged_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * slots * 32 * lanes * 2


def test_collective_matmul_ring_compiles_for_a_v5e_mesh(dp_mesh):
    """What the policy arms — the ZeRO-1 writeback ring (shard_map +
    ppermute) — and the fused RDMA primitive at an activation tile that
    fits; at the full 1024 rows the primitive is refused for VMEM."""
    from accelerate_tpu.native.kernels.collective_matmul import (
        collective_matmul,
        ring_all_gather,
    )

    sharded = NamedSharding(dp_mesh, P("dp"))
    compiled = (
        jax.jit(lambda a: ring_all_gather(a, sharded, 0))
        .lower(sds((768, 3072), BF16, sharded))
        .compile()
    )
    assert "collective-permute" in compiled.as_text()
    assert pallas_calls(compiled) == 0

    w = sds((3072, 768), BF16, sharded)

    def fused(rows):
        x = sds((rows, 3072), BF16, NamedSharding(dp_mesh, P()))
        return (
            jax.jit(lambda x, w: collective_matmul(x, w, mesh=dp_mesh, interpret=False))
            .lower(x, w)
            .compile()
        )

    assert pallas_calls(fused(256)) == 1
    with pytest.raises(Exception, match="vmem"):
        fused(1024)


def test_collective_matmul_policy_arms_on_tpu_refused_kernels_do_not(monkeypatch):
    """Arming follows the compiler: the ring is allowed on a TPU backend,
    the refused kernel raises with its words — nothing interprets."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert KernelPolicy(collective_matmul=True).interpret is False
    for name in TPU_REFUSED:
        with pytest.raises(NotImplementedError) as refused:
            KernelPolicy(**{name: True}).interpret
        assert refusal_words(name) in str(refused.value)


# ---------------------------------------------------------------------------
# the serving programs hold the KV pool in place (docs/serving.md §1)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def xl_serving_programs(one_chip):
    """``_decode_jit`` and one ``_prefill_jit`` bucket compiled for the
    described v5e at GPT-2-XL width (1600, 25 heads) and the serve cell's pool
    geometry, 2 layers deep (the loop body is the same at 48).  The
    vocabulary is cut to 2048 rows: at the published 50,304 the token table
    has six times a layer's pool of elements, and it is not a pool."""
    import accelerate_tpu.nn as nn
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
    from accelerate_tpu.models.generation import stacked_params_for_mode
    from accelerate_tpu.serving import engine, make_pools

    n_layer, slots, block, bps, num_blocks = 2, 16, 16, 64, 513  # gpt2-xl.serve-steady's pool
    nn.manual_seed(0)
    model = GPTLMHeadModel(
        GPTConfig(vocab_size=2048, n_positions=1024, n_embd=1600, n_layer=n_layer, n_head=25)
    ).eval()
    for p in model.parameters():  # what prepare(mixed_precision="bf16") serves
        p.data = p.data.astype(BF16)
    spec = model._decoder_spec()
    weights = stacked_params_for_mode(model, 0, spec.stack)
    pools = jax.eval_shape(lambda: make_pools(
        n_layer, num_blocks, spec.cfg.n_kv_head, block, spec.cfg.head_dim, BF16
    ))

    def abstract(tree):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype, one_chip), tree)

    def ints(*shape):
        return sds(shape, jnp.int32, one_chip)

    statics = dict(family=spec.family, cfg=spec.cfg, qbits=0, temperature=0.0)
    decode = engine._decode_jit.lower(
        *abstract(pools), *abstract(weights), ints(slots, bps), ints(slots), ints(slots),
        sds((slots, 2), jnp.uint32, one_chip), **statics,
    ).compile()
    prefill = engine._prefill_jit.lower(
        *abstract(pools), *abstract(weights), ints(1, 256), ints(bps), ints(),
        sds((2,), jnp.uint32, one_chip), **statics,
    ).compile()
    layer_pool_elements = int(np.prod(pools[0].shape[1:]))
    return {"decode": decode, "prefill": prefill}, layer_pool_elements


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serving_programs_move_no_pool_sized_buffer(xl_serving_programs, program):
    """The layer loop carries the pools whole and indexes them in place: no
    ``copy``, ``dynamic-slice`` or ``dynamic-update-slice`` of a layer's pool
    or more is left, inside the loop or outside it, and the donated pools
    come back in their own buffers.  (A pool with the block index on the
    lanes, scanned as ``xs``/``ys``, had eight of them a layer and two whole
    -pool copies after the loop: 44 ms of a 155 ms decode step, PERF.md.)"""
    from accelerate_tpu.telemetry.profiler import instructions_of_size

    programs, layer_pool_elements = xl_serving_programs
    text = programs[program].as_text()
    moved = instructions_of_size(
        text, ("copy", "dynamic-slice", "dynamic-update-slice"), layer_pool_elements
    )
    assert moved == []
    # the pools ARE in the program, written by the two scatters alone
    assert len(instructions_of_size(text, ("scatter",), layer_pool_elements)) == 2
    header = text.split("\n", 1)[0]
    for i in (0, 1):
        assert re.search(rf"\{{{i}\}}: \({i}, \{{\}}, (may|must)-alias\)", header), header[:400]
    if program == "decode":
        # attention reads each slot's live pages where they lie: one Mosaic
        # kernel in the scanned layer body, and nothing of the gathered span's
        # size (16 slots x 1024 positions x a token's lanes; it was built twice a
        # layer, and re-laid as (25, 1024, 64): 65 of a 72.7 ms step, PERF.md PR 32)
        from accelerate_tpu.telemetry import profiler

        assert pallas_calls(programs[program]) == 1
        opcodes = {opcode for _, _, opcode in profiler._HLO_RESULT_RE.findall(text)}
        span_sized = {dims for _, _, dims in instructions_of_size(text, opcodes, 16 * 1024 * 1600)}
        assert span_sized <= {(2, 513, 16, 1664), (2 * 513, 16, 1664)}, span_sized  # the pools alone


def _as_it_lies(dims, minor_to_major, drop=()):
    """``(sizes, order)`` of a shape without its dimensions of size 1 (and
    those in ``drop``): the sizes that are left, and the order in which they
    lie, minor first.  A layer's ``[1, 4800, 1600]{1,2,0}`` slice and the
    ``[4800, 1600]{0,1}`` bitcast of it read ``((4800, 1600), (0, 1))`` both."""
    keep = [i for i, d in enumerate(dims) if d != 1 and i not in drop]
    return tuple(dims[i] for i in keep), tuple(keep.index(i) for i in minor_to_major if i in keep)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serving_programs_read_every_weight_where_it_lies(xl_serving_programs, program):
    """At a width off the 128 lanes (1600) the device's compact layout puts a
    weight's OTHER dimension on the lanes, which is what ``h @ W.T`` wants: in
    a scanned plan's programs every weight is read once, by the product that
    uses it, as it lies.  No ``copy`` or ``transpose`` of 1600 x 1600 elements
    or more is left, and every ``dynamic-slice`` or fusion result of that size
    is a pool, or a layer's weight (or the token table) in the order its stack
    lies in.  (The head split folded into the fused qkv product re-laid 15 MB a
    layer, ``copy.23`` / ``copy.27``, and the embed's gather the whole table,
    ``copy.14`` / ``copy.17``: 3.6 of GPT-2-XL's 8.5 ms decode step, PERF.md
    PR 35.)"""
    from accelerate_tpu.telemetry.profiler import instructions_of_size

    programs, _ = xl_serving_programs
    text = programs[program].as_text()
    weight = 1600 * 1600
    assert instructions_of_size(text, ("copy", "transpose"), weight) == []

    header = text.split("\n", 1)[0]
    takes = header[header.index("entry_computation_layout"):].split("->")[0]
    lies = {}  # a weight's sizes -> the order they lie in, the layer dimension aside
    for dims, order in re.findall(r"bf16\[([\d,]+)\]\{([\d,]+)", takes):
        dims, order = [int(d) for d in dims.split(",")], [int(i) for i in order.split(",")]
        if len(dims) in (2, 3) and np.prod(dims[-2:]) >= weight:
            sizes, lie = _as_it_lies(dims, order, drop=(0,) if len(dims) == 3 else ())
            lies[sizes] = lie
    assert set(lies) == {(2048, 1600), (4800, 1600), (1600, 1600), (6400, 1600), (1600, 6400)}
    for name, _, dims in instructions_of_size(text, ("dynamic-slice", "fusion"), weight):
        if dims[-2:] == (16, 1664):
            continue  # a pool's page rows: test_serving_programs_move_no_pool_sized_buffer
        order = re.search(rf"%?{re.escape(name)} = \w+\[[\d,]*\]\{{([\d,]+)", text).group(1)
        sizes, lie = _as_it_lies(dims, [int(i) for i in order.split(",")])
        assert lies.get(sizes) == lie, (name, dims, order)


def test_a_plan_of_attention_layers_keeps_the_scan_and_takes_no_state(xl_serving_programs):
    """GPT-2's family is the plan "attention x L" (``layer_plan`` gives
    ``None``): its programs scan the one stack of layers and take no
    state-pool argument — the pools, the 4 + 12 stacked weights and four small
    inputs are all they take.  (Lowered from the
    parent commit and from this tree, the three programs' StableHLO text is
    byte for byte the same: CHANGES.md, PR 31.)"""
    import dataclasses

    from accelerate_tpu.models.generation import ATTENTION, layer_plan
    from accelerate_tpu.models.gpt import GPT_DECODER

    assert layer_plan(GPT_DECODER, None) is None
    spelled_out = dataclasses.replace(GPT_DECODER, plan=lambda cfg: (ATTENTION,) * 48)
    assert layer_plan(spelled_out, None) is None  # one kind: the scan, whoever says it
    programs, _ = xl_serving_programs
    for name, n_args in (("decode", 2 + 4 + 12 + 4), ("prefill", 2 + 4 + 12 + 4)):
        text = programs[name].as_text()
        entry = text[text.index("ENTRY"):]
        assert len(re.findall(r"\bparameter\(\d+\)", entry)) == n_args, name


# ---------------------------------------------------------------------------
# a sharded model's decode program: the kernel per device, under shard_map
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded_decode_inputs(topo):
    """What ``DecodeService`` hands the decode programs for a prepared or
    ``shard_for_inference`` model on a four-chip host: the weights sharded
    over the mesh (here over their last dimension where 4 divides it, as a
    ``tp`` plan would), the pools and the small inputs replicated on the same
    mesh.  GPT-2-small width, 2 layers, 2048 rows of vocabulary."""
    import accelerate_tpu.nn as nn
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
    from accelerate_tpu.models.generation import stacked_params_for_mode
    from accelerate_tpu.serving import make_pools

    mesh = Mesh(np.array(topo.devices).reshape(4), ("tp",))
    n_layer, slots, block, bps, num_blocks = 2, 16, 16, 64, 513
    nn.manual_seed(0)
    model = GPTLMHeadModel(
        GPTConfig(vocab_size=2048, n_positions=1024, n_embd=768, n_layer=n_layer, n_head=12)
    ).eval()
    for p in model.parameters():
        p.data = p.data.astype(BF16)
    spec = model._decoder_spec()
    pools = jax.eval_shape(lambda: make_pools(
        n_layer, num_blocks, spec.cfg.n_kv_head, block, spec.cfg.head_dim, BF16
    ))

    def whole(shape, dtype):
        return sds(shape, dtype, NamedSharding(mesh, P()))

    def weight(x):
        over = x.ndim >= 2 and x.shape[-1] % 4 == 0
        return sds(x.shape, x.dtype, NamedSharding(mesh, P(*[None] * (x.ndim - 1), "tp") if over else P()))

    args = (
        *(whole(p.shape, p.dtype) for p in pools),
        *jax.tree_util.tree_map(weight, stacked_params_for_mode(model, 0, spec.stack)),
        whole((slots, bps), jnp.int32), whole((slots,), jnp.int32), whole((slots,), jnp.int32),
        whole((slots, 2), jnp.uint32),
    )
    statics = dict(family=spec.family, cfg=spec.cfg, qbits=0, temperature=0.0)
    return mesh, args, statics, int(np.prod(pools[0].shape[1:]))


@pytest.mark.parametrize("decode_steps", [1, 8])
def test_decode_on_a_mesh_needs_shard_map_and_has_it(sharded_decode_inputs, decode_steps):
    """The decode program's attention is a Mosaic kernel and its only path:
    bare in a program over four devices the TPU lowering refuses it (the CPU
    tests interpret the kernel and cannot see this).  Given the pools' mesh
    (``engine._pool_mesh``, which ``run_decode`` and ``run_decode_n`` read off
    the committed pool) the kernel runs per device under ``shard_map`` on
    replicated operands: the program compiles, holds one kernel a scanned
    layer body, and still carries the replicated pools in place."""
    from accelerate_tpu.serving import engine
    from accelerate_tpu.telemetry.profiler import instructions_of_size

    mesh, args, statics, layer_pool_elements = sharded_decode_inputs
    program = engine._decode_jit if decode_steps == 1 else engine._decode_n_jit
    if decode_steps > 1:
        statics = dict(statics, decode_steps=decode_steps)
    with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
        program.lower(*args, **statics).compile()
    assert engine._pool_mesh(args[0]) is mesh  # what the dispatch observes
    compiled = program.lower(*args, **statics, mesh=mesh).compile()
    text = compiled.as_text()
    assert pallas_calls(compiled) == 1
    moved = instructions_of_size(
        text, ("copy", "dynamic-slice", "dynamic-update-slice", "all-gather", "all-reduce"),
        layer_pool_elements,
    )
    assert moved == []
    assert len(instructions_of_size(text, ("scatter",), layer_pool_elements)) == 2
    header = text.split("\n", 1)[0]
    for i in (0, 1):
        assert re.search(rf"\{{{i}\}}: \({i}, \{{\}}, (may|must)-alias\)", header), header[:400]


# ---------------------------------------------------------------------------
# a mixed layer plan holds both of its caches in place (docs/serving.md §layer plan)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hybrid_serving_programs(one_chip):
    """``_decode_jit`` and the 512-token ``_prefill_jit`` (the serve-chat
    cell's largest bucket) of Nemotron-H compiled for the described v5e at the published widths and the serve-chat cell's
    pools (64 slots, 3073 blocks of 16), five layers deep (``MEM*E``: the
    plan is unrolled, every layer of a kind does the same) and 2048 rows of
    vocabulary."""
    from accelerate_tpu.models.nemotron_h import NEMOTRON_H_DECODER, NemotronHConfig, layer_shapes
    from accelerate_tpu.serving import engine, make_pools, make_state_pool

    cfg = NemotronHConfig(vocab_size=2048, pattern="MEM*E", experts_held=32)
    slots, block, bps, num_blocks = 64, 16, 48, 3073

    def shapes(tree, dtype=None):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, dtype or x.dtype, one_chip), tree)

    def weights(kind):
        return {k: sds(s, BF16, one_chip) for k, s in layer_shapes(cfg, kind).items()}

    pools = shapes(jax.eval_shape(lambda: make_pools(1, num_blocks, cfg.n_kv_head, block, cfg.head_dim, BF16)))
    state = shapes(jax.eval_shape(lambda: make_state_pool(
        2, slots, (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size),
        (cfg.conv_kernel - 1, cfg.conv_width), BF16,
    )))
    layers = (tuple(weights(kind) for kind in cfg.kinds), {}, {})

    def ints(*shape):
        return sds(shape, jnp.int32, one_chip)

    statics = dict(family=NEMOTRON_H_DECODER, cfg=cfg, qbits=0, temperature=0.0)
    lowered = engine._decode_jit.lower(
        *pools, weights("globals"), layers, ints(slots, bps), ints(slots), ints(slots),
        sds((slots, 2), jnp.uint32, one_chip), state, **statics,
    )
    prefill = engine._prefill_jit.lower(
        *pools, weights("globals"), layers, ints(1, 512), ints(bps), ints(),
        sds((2,), jnp.uint32, one_chip), ints(), state, **statics,
    ).compile()
    sizes = {
        "kv": int(np.prod(pools[0].shape[1:])), "state": int(np.prod(state["ssm"].shape[1:])),
        "experts": int(np.prod(layers[0][1]["up_w"].shape)), "mamba": cfg.pattern.count("M"),
    }
    return {"decode": lowered.compile(), "prefill": prefill, "decode_lowered": lowered.as_text()}, sizes


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_programs_move_no_cache_or_expert_sized_buffer(hybrid_serving_programs, program):
    """Both caches are donated, carried whole and touched at their own rows, and
    every layer's weights are arrays of their own: no ``copy`` of a layer's KV
    pool, of a layer's state pool or of an expert stack is left, no instruction
    of the ENTRY computation holds a layer's state (the prefill's write is
    fused, in place; the decode's update is the state kernel's, on the pool),
    and the four cache buffers come back aliased.  (The expert stacks as one
    ``(L, 32, 2688, 1856)`` parameter were sliced out, 640 MB a layer a step:
    PERF.md §6.)  The experts' products are the compiler's own: the only
    custom kernel is the decode's one-token recurrence, one call a Mamba
    layer, and the prefill has none."""
    from accelerate_tpu.telemetry.profiler import instructions_of_size

    programs, sizes = hybrid_serving_programs
    text = programs[program].as_text()
    assert instructions_of_size(text, ("copy",), min(sizes["kv"], sizes["state"])) == []
    assert instructions_of_size(text, ("copy", "slice", "dynamic-slice", "transpose"), sizes["experts"]) == []
    entry = text[text.index("ENTRY"):]
    held = instructions_of_size(entry, ("slice", "dynamic-slice", "copy", "fusion", "dynamic-update-slice"), sizes["state"])
    assert all(dims == (2, 64, 64, 64, 128) for _, _, dims in held), held  # the pool itself, updated in place
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"(?:may|must)-alias", header)) == 4, header[:600]
    assert pallas_calls(programs[program]) == (sizes["mamba"] if program == "decode" else 0)
    assert "paged_attention" not in text  # an unrolled plan's attention keeps the gather


def test_a_mixed_plans_decode_lowers_the_state_kernel_once_and_updates_the_pool_in_place(
        hybrid_serving_programs):
    """Nemotron-H's one-token recurrence is ONE lowered callee
    (``native/kernels/ssm_step.py``, called through one ``jax.jit`` whose
    arguments have the same shapes at every layer) that each Mamba layer
    calls: jax lowers it once a program, so the cell's 12 Mamba layers pay for
    one Mosaic lowering.  After inlining, each layer's ``ssm_step`` call takes
    the whole state pool as its operand and hands it back aliased, and nothing
    else in the program — no ``copy``, ``slice``, ``dynamic-slice``,
    ``dynamic-update-slice``, ``transpose`` or fusion result — holds a layer's
    state or more (the plain step wrote the pool a layer, in a
    ``dynamic-update-slice`` under ``atpu_serve_ssm_step``: PERF.md §6)."""
    from accelerate_tpu.telemetry.profiler import instructions_of_size

    programs, sizes = hybrid_serving_programs
    lowered = programs["decode_lowered"]
    assert lowered.count("tpu_custom_call") == 1
    (callee,) = [re.match(r"\w+ @(\w+)", f).group(1) for f in lowered.split("func.func ")[1:] if "tpu_custom_call" in f]
    assert callee == "_step"
    assert len(re.findall(rf"call @{callee}\(", lowered)) == sizes["mamba"]
    text = programs["decode"].as_text()
    held = instructions_of_size(
        text, ("copy", "slice", "dynamic-slice", "dynamic-update-slice", "transpose", "fusion"), sizes["state"]
    )
    assert [dims for _, _, dims in held if dims[-3:] == (64, 64, 128)] == []  # (the rest: expert stacks)
    calls = [line for line in text.split("\n") if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == sizes["mamba"]
    pool = "f32[%d,64,64,64,128]" % sizes["mamba"]
    for line in calls:
        assert "ssm_step" in line and line.split("=", 1)[1].lstrip().startswith(f"({pool}")
        assert "output_to_operand_aliasing={{0}: (7, {})}" in line


@pytest.mark.parametrize("devices", [1, 4], ids=["one-chip", "four-chip-mesh"])
def test_the_state_kernel_compiles_in_mosaic_for_v5e(topo, devices):
    """The one-token recurrence at the serve-chat cell's shapes: a pool of 12
    layers x 64 slots of 64 x 64 x 128 float32 (1.61 GB), one slot's 2 MiB
    state in and out of VMEM a grid step, within the VMEM a kernel gets by
    default (the compiler refuses one that is not).  No temporary but the
    slots' small rows.  On a mesh of four devices the pool lies replicated, as
    the service commits a sharded model's pools, and the kernel runs per
    device under ``shard_map`` (bare, the TPU lowering refuses it)."""
    from accelerate_tpu.native.kernels import ssm_step as kernel

    mesh = Mesh(np.array(topo.devices[:devices]).reshape(devices), ("tp",))
    whole = NamedSharding(mesh, P())
    n_layers, slots, h, p, n, g = 12, 64, 64, 64, 128, 8
    f32 = jnp.float32
    args = (sds((n_layers, slots, h, p, n), f32, whole), sds((), jnp.int32, whole), sds((slots,), jnp.bool_, whole),
            sds((slots, h, p), f32, whole), sds((slots, h), f32, whole), sds((h,), f32, whole),
            sds((slots, g, n), f32, whole), sds((slots, g, n), f32, whole), sds((h,), f32, whole))
    compiled = jax.jit(
        lambda pool, i, *rest: kernel.ssm_step_live(pool, i, *rest, mesh=mesh), donate_argnums=0
    ).lower(*args).compile()
    assert pallas_calls(compiled) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * slots * h * p * 4
    assert re.search(r"\{1\}: \(0, \{\}, (may|must)-alias\)", compiled.as_text().split("\n", 1)[0])


_SERVE_A_MIXED_PLAN = """
import sys

import numpy as np

import accelerate_tpu.serving.engine as engine
from accelerate_tpu import DecodeService, ServingConfig
from accelerate_tpu.models.nemotron_h import NemotronHConfig, NemotronHForCausalLM

cfg = NemotronHConfig(
    vocab_size=96, hidden_size=32, pattern="MEM*EM", mamba_num_heads=4, mamba_head_dim=8,
    n_groups=2, ssm_state_size=8, chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, n_routed_experts=8, experts_held=4, num_experts_per_tok=3,
    moe_intermediate_size=16, moe_shared_expert_intermediate_size=24, max_position_embeddings=128,
)
service = DecodeService(
    NemotronHForCausalLM(cfg).eval(),
    ServingConfig(max_slots=2, block_size=4, prompt_bucket=16, max_request_len=64),
)
for n in (5, 19):
    service.submit(np.arange(n, dtype=np.int32) % 96, max_new_tokens=6)
service.run()
assert len(service.results) == 2 and engine._decode_jit._cache_size() == 1
print("pallas" if "jax.experimental.pallas" in sys.modules else "no pallas",
      "attention kernel" if "accelerate_tpu.native.kernels.paged_attention" in sys.modules else "no attention kernel")
"""


def test_a_process_that_serves_a_mixed_plan_never_imports_pallas():
    """Never for its attention: the decode attention kernel is the scanned
    plan's, imported in its branch of ``decode_layer`` at trace time, and an
    unrolled plan's attention layers keep the gather (a kernel lowering an
    unrolled attention layer and the whole import were what ``setup_s``
    refused once, PERF.md §6).  A fresh interpreter that imports the
    engine, builds a service over Nemotron-H's unrolled plan and serves two
    requests through its prefill and decode programs imports Pallas once, at
    its decode's first trace, for the Mamba layers' one shared lowering of the
    state kernel, and never the attention kernel's module."""
    import os
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # one device: the service's own default
    proc = subprocess.run(
        [sys.executable, "-c", _SERVE_A_MIXED_PLAN], env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "pallas no attention kernel"


# ---------------------------------------------------------------------------
# a mixed plan scanned by its period: the kernel, and both caches in place
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def periodic_serving_programs(one_chip):
    """``_decode_jit`` and the 256-token ``_prefill_jit`` of Olmo-Hybrid compiled
    for the described v5e at the published widths and the serve-longout cell's
    pools (48 slots, 3073 blocks of 16, 96 blocks a slot), the cell's four
    periods deep (``[linear, linear, linear, full] x 4``: 12 linear layers)
    and 2048 rows of vocabulary."""
    from accelerate_tpu.models.olmo_hybrid import (
        _PERIOD,
        OLMO_HYBRID_DECODER,
        OlmoHybridConfig,
        layer_shapes,
    )
    from accelerate_tpu.ops import delta_rule
    from accelerate_tpu.serving import engine, make_pools, make_state_pool

    cfg = OlmoHybridConfig(vocab_size=2048, layer_types=_PERIOD * 4)
    slots, block, bps, num_blocks, repeats = 48, 16, 96, 3073, 4

    def shapes(tree):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype, one_chip), tree)

    def weights(kind, lead=()):
        return {k: sds((*lead, *s), BF16, one_chip) for k, s in layer_shapes(cfg, kind).items()}

    pools = shapes(jax.eval_shape(lambda: make_pools(repeats, num_blocks, cfg.n_kv_head, block, cfg.head_dim, BF16)))
    heads, d_k, d_v = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    packed = jax.eval_shape(delta_rule.pack_state, sds((heads, d_k, d_v), jnp.float32, one_chip)).shape
    state = shapes(jax.eval_shape(lambda: make_state_pool(
        3 * repeats, slots, packed, (cfg.linear_conv_kernel_dim - 1, cfg.conv_width), BF16,
    )))
    layers = (tuple(weights(kind, (repeats,)) for kind in cfg.kinds[:4]), {}, {})

    def ints(*shape):
        return sds(shape, jnp.int32, one_chip)

    statics = dict(family=OLMO_HYBRID_DECODER, cfg=cfg, qbits=0, temperature=0.0)
    lowered = engine._decode_jit.lower(
        *pools, weights("globals"), layers, ints(slots, bps), ints(slots), ints(slots),
        sds((slots, 2), jnp.uint32, one_chip), state, **statics,
    )
    prefill = engine._prefill_jit.lower(
        *pools, weights("globals"), layers, ints(1, 256), ints(bps), ints(),
        sds((2,), jnp.uint32, one_chip), ints(), state, **statics,
    ).compile()
    sizes = {
        "span": slots * bps * block * cfg.n_kv_head * cfg.head_dim,  # what the gather path would build a pool and layer
        "state": slots * heads * d_k * d_v, "kv": int(np.prod(pools[0].shape[1:])),
        "weight": cfg.hidden_size * cfg.key_width, "state_layers": 3 * repeats, "repeats": repeats,
    }
    return {"decode": lowered.compile(), "prefill": prefill, "decode_lowered": lowered.as_text()}, sizes


def test_a_periodic_plans_decode_holds_nothing_of_the_gathered_spans_size(periodic_serving_programs):
    """The plan is scanned by its period, so its attention layer takes the
    kernel: ONE paged-attention call in the program (the scan's body, beside
    its three linear layers' delta-rule step), the pools left
    where they lie — nothing the size of the span the gather path would build
    (48 slots x 1536 positions x 3840 lanes, 566 MB a pool and layer) is
    copied, gathered, sliced or re-laid, the program's temporaries are under a
    sixteenth of that, and no weight is copied or transposed (the gate's head
    split folded into its product re-laid 44 MB a linear layer a step until
    ``models/olmo_hybrid.py::_gdn_in`` held it behind a barrier)."""
    from accelerate_tpu.telemetry.profiler import instructions_of_size

    programs, sizes = periodic_serving_programs
    decode = programs["decode"]
    text = decode.as_text()
    calls = [line for line in text.split("\n") if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted("paged_attention" if "paged_attention" in c else "gdn_step" if "gdn_step" in c else c
                  for c in calls) == ["gdn_step"] * 3 + ["paged_attention"]
    moved = instructions_of_size(
        text, ("copy", "gather", "slice", "dynamic-slice", "transpose", "concatenate", "reshape"), sizes["span"] // 2)
    assert moved == []
    assert decode.memory_analysis().temp_size_in_bytes < sizes["span"] * 2 // 16
    assert instructions_of_size(text, ("copy", "transpose"), sizes["weight"]) == []


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_a_periodic_plans_programs_update_both_caches_in_place(periodic_serving_programs, program):
    """Both pools ride the scan's carry and come back aliased; no ``copy`` of a
    layer's state or of a layer's KV pool is left, and outside the fusions that
    read a layer's rows and write them back in place nothing holds a layer's
    state (decode: the delta-rule kernel's operand and result are the pool
    itself; prefill: the write of the admitted slot's rows is fused)."""
    from accelerate_tpu.telemetry.profiler import instructions_of_size

    programs, sizes = periodic_serving_programs
    text = programs[program].as_text()
    assert instructions_of_size(text, ("copy",), min(sizes["state"], sizes["kv"])) == []
    pool = (sizes["state_layers"], 48, 30, 48, 384)
    top = "\n".join(
        block for block in re.split(r"\n(?=\S)", text) if "fused_computation" not in block.split("\n", 1)[0]
    )
    held = instructions_of_size(
        top, ("slice", "dynamic-slice", "copy", "fusion", "dynamic-update-slice", "transpose"), sizes["state"])
    assert all(dims in (pool, (3073 * sizes["repeats"], 16, 3840)) for _, _, dims in held), held
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"(?:may|must)-alias", header)) == 4, header[:600]


def test_the_state_pool_lies_without_padding(periodic_serving_programs):
    """``(96, 192)`` float32 a head would lie on 256 lanes, a third more memory
    and bytes a step; packed two k rows a row of lanes, ``(48, 384)``, the pool
    as the compiler lays it out is within 2% of slots x layers x 30 x 96 x 192 x
    4 bytes."""
    programs, sizes = periodic_serving_programs
    header = programs["decode"].as_text().split("\n", 1)[0]
    takes = header[header.index("entry_computation_layout"):].split("->")[0]
    (dims, tile), = re.findall(r"f32\[(%d,48,[\d,]+)\]\{[\d,]+:T\(([\d,]+)\)" % sizes["state_layers"], takes)
    dims, tile = [int(d) for d in dims.split(",")], [int(t) for t in tile.split(",")]
    for axis, t in zip((-2, -1), tile):
        dims[axis] = -(-dims[axis] // t) * t
    laid_out = 4 * int(np.prod(dims))
    assert abs(laid_out / (4 * sizes["state_layers"] * sizes["state"]) - 1) < 0.02


def test_a_periodic_plans_decode_lowers_the_delta_rule_kernel_once_and_updates_the_pool_in_place(
        periodic_serving_programs):
    """Olmo-Hybrid's one-token delta rule is ONE lowered callee
    (``native/kernels/gdn_step.py``, called through one ``jax.jit`` whose
    arguments have the same shapes at every linear layer) that the scan's body
    calls once a linear layer: three calls a period, run four times, so the
    cell's 12 linear layers run 12 ``tpu_custom_call``s a step from one Mosaic
    lowering.  Each takes the whole state pool as its operand and hands it back
    aliased, and nothing under ``atpu_serve_gdn_step`` — no
    ``dynamic-update-slice``, ``slice``, ``copy`` or fusion — holds a layer's
    state or more (the plain step read the state twice and wrote it back with
    a pool-sized ``select_dynamic-update-slice_fusion`` a layer: PERF.md §5)."""
    from accelerate_tpu.telemetry.profiler import instructions_of_size

    programs, sizes = periodic_serving_programs
    lowered = programs["decode_lowered"]
    callees = {re.match(r"\w+ @(\w+)", f).group(1): f for f in lowered.split("func.func ")[1:] if "tpu_custom_call" in f}
    assert lowered.count("stablehlo.custom_call @tpu_custom_call") == 2  # one lowering a kernel: attention's, this
    pool_type = "tensor<%dx48x30x48x384xf32>" % sizes["state_layers"]
    (callee,) = [name for name, body in callees.items() if f"-> ({pool_type}" in body]
    assert callee == "_gdn_step"
    assert len(re.findall(rf"call @{callee}\(", lowered)) == 3  # the scan's body: one a linear layer of the period
    text = programs["decode"].as_text()
    calls = [line for line in text.split("\n") if 'custom_call_target="tpu_custom_call"' in line and "gdn_step" in line]
    assert len(calls) == 3
    # the scan over the repeats: its condition compares the counter with a constant
    loop = re.search(r" while\(.*condition=(%[\w.]+), body=(%[\w.]+)", text)
    blocks = {b.split(" ", 1)[0]: b for b in re.split(r"\n(?=\S)", text)}
    (trips,) = re.findall(r"s32\[\]\S* constant\((\d+)\)", blocks[loop.group(1)])
    body = blocks[loop.group(2)]
    assert all(line in body for line in calls)
    assert int(trips) * len(calls) == 3 * sizes["repeats"] == 12
    pool = "f32[%d,48,30,48,384]" % sizes["state_layers"]
    for line in calls:
        assert line.split("=", 1)[1].lstrip().startswith(f"({pool}")
        assert "output_to_operand_aliasing={{0}: (6, {})}" in line
    scoped = "\n".join(line for line in text.split("\n") if "atpu_serve_gdn_step" in line)
    held = instructions_of_size(
        scoped, ("copy", "slice", "dynamic-slice", "dynamic-update-slice", "transpose", "fusion"), sizes["state"])
    assert held == []


@pytest.mark.parametrize("devices", [1, 4], ids=["one-chip", "four-chip-mesh"])
def test_the_delta_rule_kernel_compiles_in_mosaic_for_v5e(topo, devices):
    """The one-token delta rule at the serve-longout cell's shapes: a pool of
    12 linear layers x 48 slots x 30 heads of 96 x 192 float32, packed (48,
    384) (1.27 GB), one slot's 2.2 MB state in and out of VMEM a grid step
    (8.8 MB double-buffered, under the 16 MiB scoped default), ``exp(g)``,
    ``β`` and ``k·q`` in SMEM; no temporary but the slots' small rows.  On a
    mesh of four devices the pool lies replicated and the kernel runs per
    device under ``shard_map`` (bare, the TPU lowering refuses it)."""
    from accelerate_tpu.native.kernels import gdn_step as kernel

    mesh = Mesh(np.array(topo.devices[:devices]).reshape(devices), ("tp",))
    whole = NamedSharding(mesh, P())
    n_layers, slots, h, d_k, d_v = 12, 48, 30, 96, 192
    f32 = jnp.float32
    args = (sds((n_layers, slots, h, 48, 384), f32, whole), sds((), jnp.int32, whole),
            sds((slots,), jnp.bool_, whole), sds((slots, h, d_k), f32, whole), sds((slots, h, d_k), f32, whole),
            sds((slots, h, d_v), f32, whole), sds((slots, h), f32, whole), sds((slots, h), f32, whole))
    compiled = jax.jit(
        lambda pool, i, *rest: kernel.gdn_step_live(pool, i, *rest, mesh=mesh), donate_argnums=0
    ).lower(*args).compile()
    assert pallas_calls(compiled) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * slots * h * d_v * 4
    assert re.search(r"\{1\}: \(0, \{\}, (may|must)-alias\)", compiled.as_text().split("\n", 1)[0])
