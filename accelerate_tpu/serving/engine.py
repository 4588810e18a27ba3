"""Captured prefill/decode programs over the paged KV pool (docs/serving.md).

Two separately jitted programs, split so a long incoming prompt never
stalls token streaming for in-flight sequences:

* ``run_prefill`` — ONE request's bucket-padded prompt through the layer
  scan; writes its k/v into the pool blocks the scheduler reserved (one
  scatter of whole pages a layer) and samples the request's first token.
  One compiled variant per ``(bucket_len, mode)`` — prompt lengths are
  bucketed by the scheduler (``kv_blocks.bucket_length``), the TRUE length
  rides as a traced scalar.
* ``run_decode_n`` — the WHOLE slot batch ``decode_steps`` tokens forward
  inside ONE captured program: each micro-step embeds the slot's current
  token at its own position, scatters the new k/v into the pool
  (``block_tables[slot][pos // bs]`` at offset ``pos % bs``), attends over
  each slot's LIVE pages where they lie (one Mosaic kernel a layer,
  ``native/kernels/paged_attention.py``: no gathered span, no relayout, no
  product over dead positions), samples — and feeds the sampled token
  back into the next micro-step's embed IN-PROGRAM, advancing positions
  in-program too.  The host sees one ``(slots, n)`` token block per
  dispatch instead of one scalar per token: dispatch overhead and the
  per-token host sync amortize ``n``-fold (the device-resident hot loop,
  docs/serving.md §device-resident decode).  ``decode_steps=1`` is the
  degenerate loop — the body inlined once, no ``scan`` wrapper, exactly
  the classic one-token program.  Every shape is fixed at service
  construction, so the steady state is exactly one program, replayed.

Both reuse the single-request engine's contracts wholesale: the
``DecoderFamily`` pure math, ``stacked_params_for_mode`` (so int8/int4
quantized weight modes compose — the stacks are shared with ``generate()``),
``_dequant_layer`` widening inside the scan, and ``cached_attention``'s
mathematics: prefill calls it on the bucket's fresh k/v, decode's kernel
states the same q·k in float32, the same mask formula at the same true
positions and the same float32 softmax, summed in chunks of pages.  So
serving's greedy tokens are per-sequence the same as a single-request
``generate()``'s on the tests' models, and its logits agree to float32
summation order — not bitwise (tests/test_serving.py holds both).

Pools are DONATED through both programs and the layer scan CARRIES them
whole, as ``(L·NB, bs, n_kv·d)`` page rows (``_page_rows``: layer ``l``'s
block ``b`` is row ``l·NB + b``), beside the layer counter: each layer
scatters into the carried buffer at its own rows, in place, and its
attention kernel reads its pages from the same buffer, left in HBM.
They are never the scan's ``xs``/``ys`` — that slices every layer's pool out
of the stack and writes it back, and with a ``[…, bs, d]`` page the chip's
compiler also put the block index on the lanes and transposed the layer's
whole pool around every access: 44 ms of a 155 ms GPT-2-XL decode step, and
the same in every prefill (PERF.md, PR 28).  A page is one lane-dense
``[bs, lanes]`` slab (``kv_blocks.page_lanes``: a token's ``n_kv·d``, zeros up
to whole tiles), which the kernel takes as it lies.  No pool-sized copy, slice
or update is left in either program, and nothing of the gathered span's size
in the decode program (held against the chip's compiler by
tests/test_tpu_compile.py).

The multi-token program's positions/tokens/rng streams are returned (the scheduler owns them as
committed device arrays and feeds each call's outputs into the next, so a
steady-state ``decode_steps > 1`` step uploads NOTHING host→device —
regression-pinned with a ``jax.transfer_guard`` in tests/test_serving.py)
but deliberately NOT donated: they are scan carries whose final values
alias slices of the stacked token-block output, and donating them tripped
an allocation-dependent XLA:CPU buffer-aliasing corruption — the donated
input buffer was reused for one output while another output still read it,
silently freezing degenerate sequences mid-stream in SOME processes (the
per-process coin flip came from allocator layout).  They are three tiny
int arrays; the copy costs nothing.  The single-token program keeps the
per-step mirror uploads (see ``_decode_jit``).

Both programs walk the family's **layer plan** (``models.generation.layer_plan``;
docs/serving.md §layer plan).  A plan of attention layers is the scan above
and nothing else.  A mixed plan (recurrent and expert layers among them) takes
a second donated cache — the per-slot state pool of
``kv_blocks.make_state_pool``, updated at each layer's rows in place like the
KV pool — and returns it with the expert layers' summed load beside the tokens.
How it is walked follows from the plan and from how the family holds its
weights (``models.generation.plan_period``): a dict a layer is unrolled by
``_walk_plan``; a plan that is a whole number of repeats of one period, held as
one stack per position in the period, is scanned by ``_scan_periods`` — one
``lax.scan`` over the repeats whose body walks one period, both pools in the
carry, each program compiling one period.

**The plan also decides how a decode layer attends** (``decode_layer``), and
nothing else does — no argument, no policy, no model's name: what is scanned
takes the kernel above, what is unrolled takes the gather.  Unrolled it is what
it was: gather the slot's whole table row, re-lay it as ``(Hkv, S, d)``,
``cached_attention`` over all of it.  Few of Nemotron-H's layers attend, over
few kv heads (3 of 26, 2 heads: 0.94 of a 20.7 ms step), and unrolled they
would pay one kernel lowering each and the Pallas import at every start; so the
kernel module is imported in the scan's branch at trace time, and an unrolled
plan's attention layers lower to the text they lowered to before the kernel
came (PERF.md §6).  (A recurrent layer's decode step is the family's own:
Mamba-2's and the delta rule's each take a kernel over the live slots, one
lowering for all layers of the kind — ``recurrent`` in ``_decode_body``.)  A
period's attention layers sit in a scan's body: one lowering a program, and a family with 30 kv heads of 128 could not be served by the gather at all
(4.5 GB gathered and re-laid a step: PERF.md, PR 36).  The
writers pad a token's row to the page's lanes only where the page has pad
lanes (``_pad_lanes``), so a family whose ``n_kv·d`` is a multiple of 128
traces the same writes either way.

**Weights are read where they lie** (docs/serving.md has the section).  The
layer scan hands a family's ``attn_in`` / ``attn_out`` layer ``l``'s slice of
each stack, and the programs are meant to read it once, by the product that
uses it, in the layout in which it lies on the device: compact, which at a
width off the 128 lanes (GPT-2-XL's 1600) puts a weight's OTHER dimension on
the lanes, as ``h @ W.T`` wants it.  What breaks that is in a family's own
functions, not here: a head split folded into a fused product re-lays the
layer's whole weight (3.1 of an 8.5 ms GPT-2-XL step), a gather of rows from a
table that lies across the lanes copies the table (0.5 ms).  GPT-2's decode
family states both otherwise (``models/gpt.py``); tests/test_tpu_compile.py
holds that no ``copy`` or ``transpose`` of a weight's size is left in either
program, and that every weight-sized slice lies as its stack does.

Zero-recompile forensics: the scheduler routes every call through
:class:`CompileWatcher`, which diffs the jit cache size around the call.
First compiles of a not-yet-seen signature are warmup; any growth on a seen
signature is an anomaly, counted and emitted as a ``kind="serving"``
:class:`~..telemetry.RecompileEvent` through the telemetry hub — the
regression guard the tests' and ``chip_smoke.py``'s assertions read.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..models.generation import (
    ATTENTION,
    RECURRENT,
    DecoderFamily,
    _dequant_layer,
    cached_attention,
    layer_plan,
    plan_period,
)


def _page_rows(pool):
    """The ``(L, NB, bs, n_kv·d)`` pool as ``(L·NB, bs, n_kv·d)`` page rows:
    layer ``l``'s block ``b`` is row ``l·NB + b``.  A leading-dimension
    reshape — free, and what lets the layer loop carry the pool whole and
    index it in place instead of slicing a layer's pool out and back."""
    return pool.reshape(-1, *pool.shape[2:])


def _pad_lanes(rows, pool):
    """``rows``' last dimension, a token's ``n_kv·d``, up to the lanes of
    ``pool``'s pages (``kv_blocks.page_lanes``) with zeros.  Decided on the
    static shapes: where the page has no pad lanes (``n_kv·d`` a multiple of
    128) this is ``rows`` itself, and the writers trace what they always
    did."""
    pad = pool.shape[-1] - rows.shape[-1]
    if pad == 0:
        return rows
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, pad),))


def _walk_plan(kinds, layers, x, kp, vp, state, attention, recurrent, ffn, first=None):
    """The layers of a MIXED plan, one after the other: ``layers[j]`` is layer
    ``j``'s own weights, and the layer does what its kind does to the
    activations and to its own cache — ``attention(l, x, kp, vp, i)`` the paged
    pool, ``recurrent(l, x, state, i)`` the state pool, ``i`` its rank among
    its kind; ``ffn(l, x)`` neither.  A plan held a dict a layer is walked
    whole by one call: unrolled, because its runs of one kind are one or two
    layers long, 26 layers compile in seconds, and a static rank lets every
    layer touch its rows of both pools in place.  A scanned period
    (``_scan_periods``) is one call a repeat, ``first[kind]`` the traced rank
    of the repeat's first layer of that kind.  Returns ``(x, kp, vp, state,
    load)``, ``load`` the ffn layers' loads summed."""
    seen, load = {}, None
    for kind, l in zip(kinds, layers, strict=True):
        i = seen[kind] = seen.get(kind, -1) + 1
        if first is not None:
            i = first[kind] + i
        if kind == ATTENTION:
            x, kp, vp = attention(l, x, kp, vp, i)
        elif kind == RECURRENT:
            x, state = recurrent(l, x, state, i)
        else:
            x, got = ffn(l, x)
            load = got if load is None else load + got
    return x, kp, vp, state, load


def _scan_periods(kinds, stacks, x, kp, vp, state, attention, recurrent, ffn):
    """A mixed plan that is ``L / period`` repeats of its first ``period``
    kinds, its weights held as one stack per position in the period: ONE
    ``lax.scan`` over the repeats whose body is ``_walk_plan`` over one period.
    Both pools are carried whole, as the scan of an all-attention plan carries
    the KV pool, and each layer touches its own rows of them in place, its rank
    among its kind counted from the repeat's number.  A program compiles one
    period, and its attention layers are in a scan: they take the kernel
    (``decode_layer``)."""
    per = kinds[: len(stacks)]

    def one_period(carry, layers):
        x, kp, vp, state, n = carry
        first = {kind: n * per.count(kind) for kind in set(per)}
        x, kp, vp, state, load = _walk_plan(
            per, layers, x, kp, vp, state, attention, recurrent, ffn, first
        )
        return (x, kp, vp, state, n + 1), load

    (x, kp, vp, state, _), loads = jax.lax.scan(
        one_period, (x, kp, vp, state, jnp.int32(0)), stacks
    )
    return x, kp, vp, state, None if loads is None else loads.sum(axis=0)


def _run_plan(kinds, layers, *rest):
    """A mixed plan by how its weights are held (``plan_period``): a dict a
    layer is unrolled, a stack per position in its period is scanned."""
    scanned = plan_period(kinds, len(layers)) is not None
    return (_scan_periods if scanned else _walk_plan)(kinds, layers, *rest)


@partial(
    jax.jit,
    static_argnames=("family", "cfg", "qbits", "temperature"),
    donate_argnums=(0, 1, 9),
)
def _prefill_jit(
    k_pool,
    v_pool,
    g,
    layers,
    padded_ids,  # (1, bucket_len) int32, prompt padded to its bucket
    block_row,  # (blocks_per_slot,) int32 — this slot's pool blocks
    prompt_len,  # () int32 TRUE length; dynamic, so one program per bucket
    rng,
    slot=None,  # () int32 — the slot whose state this prefill writes (mixed plans)
    state=None,  # the state pool (kv_blocks.make_state_pool), donated; None
    # for a plan of attention layers, whose program it leaves as it was
    *,
    family: DecoderFamily,
    cfg,
    qbits: int,
    temperature: float,
):
    bucket_len = padded_ids.shape[1]
    pool_shape = k_pool.shape
    num_blocks, block_size = pool_shape[1], pool_shape[2]
    n_blocks = bucket_len // block_size  # scheduler guarantees divisibility
    positions = jnp.arange(bucket_len)
    plain_layers, q_layers, s_layers = layers
    kp, vp = _page_rows(k_pool), _page_rows(v_pool)

    # the atpu_serve_* scopes are HLO metadata only (numerics untouched): a
    # device trace is split by them (docs/telemetry.md §spans and scopes)
    def prefill_layer(carry, l_parts):
        x, kp, vp, layer = carry
        with jax.named_scope("atpu_serve_qkv"):
            l = _dequant_layer(*l_parts, qbits, x.dtype)
            q, k, v = family.attn_in(l, x, positions, cfg)
        with jax.named_scope("atpu_serve_attend"):
            att = cached_attention(q, k, v, positions, cfg)
        with jax.named_scope("atpu_serve_kv_write"):
            # the bucket covers whole blocks: write them with one scatter each,
            # a page being the block's (bs, n_kv·d) slab.  Positions >=
            # prompt_len hold pad-token k/v — invisible behind the causal mask
            # until the decode loop overwrites them with real tokens
            rows = layer * num_blocks + block_row[:n_blocks]
            kb = k[0].transpose(1, 0, 2).reshape(n_blocks, block_size, -1)
            vb = v[0].transpose(1, 0, 2).reshape(n_blocks, block_size, -1)
            kp = kp.at[rows].set(_pad_lanes(kb, kp).astype(kp.dtype))
            vp = vp.at[rows].set(_pad_lanes(vb, vp).astype(vp.dtype))
        with jax.named_scope("atpu_serve_out_mlp"):
            return (family.attn_out(l, x, att, cfg), kp, vp, layer + 1), None

    with jax.named_scope("atpu_serve_embed"):
        x = family.embed(g, padded_ids, positions, cfg)
    kinds = layer_plan(family, cfg)
    if kinds is None:
        (x, kp, vp, _), _ = jax.lax.scan(
            prefill_layer, (x, kp, vp, jnp.int32(0)), (plain_layers, q_layers, s_layers)
        )
    else:
        def attention(l, x, kp, vp, i):
            (x, kp, vp, _), _ = prefill_layer((x, kp, vp, i), (l, {}, {}))
            return x, kp, vp

        def recurrent(l, x, state, i):
            # from a zero state, whatever the slot held: this write IS the
            # slot's reset.  Padding moves neither the state nor the tail
            x, new, tail = family.recurrent_prefill(l, x, prompt_len, cfg)
            with jax.named_scope(family.recurrent_scopes[0]):
                return x, {
                    "ssm": state["ssm"].at[i, slot].set(new),
                    "conv": state["conv"].at[i, slot].set(tail.astype(state["conv"].dtype)),
                }

        valid = (positions < prompt_len)[None]
        x, kp, vp, state, load = _run_plan(
            kinds, plain_layers, x, kp, vp, state, attention, recurrent,
            lambda l, x: family.ffn(l, x, valid, cfg),
        )
    with jax.named_scope("atpu_serve_head"):
        # logits at the TRUE last prompt position (finalize reads x[:, -1], so
        # hand it the one gathered position) — identical math to an unpadded
        # prefill's last position
        x_last = jax.lax.dynamic_slice_in_dim(x, prompt_len - 1, 1, axis=1)
        logits = family.finalize(g, x_last, cfg)  # (1, V)
        if temperature == 0.0:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            rng_out = rng
        else:
            rng_out, key = jax.random.split(rng)
            tok = jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)
    if kinds is None:
        return kp.reshape(pool_shape), vp.reshape(pool_shape), tok[0], rng_out
    return kp.reshape(pool_shape), vp.reshape(pool_shape), tok[0], rng_out, state, load


def _decode_body(
    k_pool,
    v_pool,
    g,
    layers,
    block_tables,  # (slots, blocks_per_slot) int32
    positions,  # (slots,) int32 — position of the token being fed
    tokens,  # (slots,) int32 — last sampled token per slot
    rngs,  # (slots, 2) uint32 — per-slot RNG streams
    state=None,  # the state pool of a mixed plan (see _prefill_jit)
    *,
    family: DecoderFamily,
    cfg,
    qbits: int,
    temperature: float,
    mesh=None,  # the pools' mesh, where it has several devices (_pool_mesh)
):
    """ONE token for the whole slot batch — the micro-step body shared by
    every ``decode_steps`` variant, so an n-token block is bitwise the same
    math as n single-token dispatches (the parity contract).  A mixed plan
    also returns its state pool and the ffn layers' summed load."""
    pool_shape = k_pool.shape
    num_blocks, block_size = pool_shape[1], pool_shape[2]
    plain_layers, q_layers, s_layers = layers
    kp, vp = _page_rows(k_pool), _page_rows(v_pool)
    # the plan decides how a layer attends (decode_layer): under a scan (every
    # layer attention, or a mixed plan scanned by its period) the kernel
    kinds = layer_plan(family, cfg)
    scanned = kinds is None or plan_period(kinds, len(plain_layers)) is not None

    # the atpu_serve_* scopes are HLO metadata only (numerics untouched): a
    # device trace is split by them (docs/telemetry.md §spans and scopes)
    with jax.named_scope("atpu_serve_embed"):
        # per-slot embed at the slot's OWN position (family.embed broadcasts
        # one position vector over the batch, which is exactly wrong here)
        x = jax.vmap(lambda t, p: family.embed(g, t[None, None], p[None], cfg)[0])(
            tokens, positions
        )  # (slots, 1, c)

    def decode_layer(carry, l_parts):
        x, kp, vp, layer = carry
        base = layer * num_blocks  # this layer's first page row
        with jax.named_scope("atpu_serve_qkv"):
            l = _dequant_layer(*l_parts, qbits, x.dtype)
            q, k, v = jax.vmap(
                lambda x_s, p_s: family.attn_in(l, x_s[None], p_s[None], cfg)
            )(x, positions)
            q, k, v = q[:, 0], k[:, 0], v[:, 0]  # (slots, H|Hkv, 1, d)
            n_kv, d = k.shape[1], k.shape[3]
        with jax.named_scope("atpu_serve_kv_write"):
            # scatter each slot's new k/v (one (n_kv·d) row, zeros on the
            # page's pad lanes) into its current page, in place on the carried
            # pool.  Inactive slots' tables point at trash block 0, so the
            # unconditional write (and any duplicate trash indices) never
            # touches live cache
            blk = base + jnp.take_along_axis(
                block_tables, (positions // block_size)[:, None], axis=1
            )[:, 0]
            off = positions % block_size
            k_row = _pad_lanes(k[:, :, 0, :].reshape(-1, n_kv * d), kp)
            kp = kp.at[blk, off].set(k_row.astype(kp.dtype))
            v_row = _pad_lanes(v[:, :, 0, :].reshape(-1, n_kv * d), vp)
            vp = vp.at[blk, off].set(v_row.astype(vp.dtype))

        if scanned:
            # a scanned plan: each slot's live pages, read where they lie,
            # under a running softmax — one Mosaic kernel a layer, no gathered
            # span (docs/serving.md §decode attention).  Imported here, at
            # trace time: an unrolled plan never lowers it
            from ..native.kernels.paged_attention import paged_attention

            with jax.named_scope("atpu_serve_attend"):
                att = paged_attention(
                    q[:, :, 0, :], kp, vp, block_tables, positions, base, cfg,
                    n_kv=n_kv, mesh=mesh,
                )[:, :, None, :]  # (slots, H, 1, d)
        else:
            # an unrolled plan's attention layers: gather the slot's whole
            # table row and attend over it.  Few of its layers attend, over
            # few kv heads: the kernel would buy 0.75 ms of a 20.7 ms step and
            # cost a lowering a layer at every start (PERF.md, PR 33).
            # Two vmaps where one would do, so that each phase's scope sits
            # OUTSIDE its vmap: a scope entered inside reads ``vmap(<scope>)``
            # in the op's path and is lost to the map.  Same batched
            # primitives in the same order either way
            def token_lanes(pages):  # a token's n_kv·d, without the pad lanes
                return pages if pages.shape[-1] == n_kv * d else pages[..., : n_kv * d]

            def gather_one(row):
                # gather this slot's pages: table order IS logical order, so
                # the flattened view is a virtually contiguous cache and the
                # plain causal mask applies unchanged
                kc = token_lanes(kp[base + row]).reshape(-1, n_kv, d).transpose(1, 0, 2)
                vc = token_lanes(vp[base + row]).reshape(-1, n_kv, d).transpose(1, 0, 2)
                return kc, vc  # (Hkv, S, d)

            def attend_one(q_s, kc, vc, p_s):
                return cached_attention(q_s[None], kc[None], vc[None], p_s[None], cfg)[0]

            with jax.named_scope("atpu_serve_kv_gather"):
                kc, vc = jax.vmap(gather_one)(block_tables)
            with jax.named_scope("atpu_serve_attend"):
                att = jax.vmap(attend_one)(q, kc, vc, positions)  # (slots, H, 1, d)
        with jax.named_scope("atpu_serve_out_mlp"):
            x = jax.vmap(lambda x_s, a_s: family.attn_out(l, x_s[None], a_s[None], cfg)[0])(
                x, att
            )
        return (x, kp, vp, layer + 1), None

    if kinds is None:
        (x, kp, vp, _), _ = jax.lax.scan(
            decode_layer, (x, kp, vp, jnp.int32(0)), (plain_layers, q_layers, s_layers)
        )
    else:
        # a live slot's table starts at a block of its own, a dead one's at
        # the trash block: dead slots' tokens go to no expert
        live = (block_tables[:, 0] > 0)[:, None]

        def attention(l, x, kp, vp, i):
            # dead slots write their k/v to the trash block, which live slots'
            # tables name past their own pages: a masked key is weighted 0.0,
            # and 0 * NaN is NaN, so what a dead slot's state made of its
            # activations must not get there
            x = jnp.where(live[:, :, None], x, 0)
            (x, kp, vp, _), _ = decode_layer((x, kp, vp, i), (l, {}, {}))
            return x, kp, vp

        def recurrent(l, x, state, i):
            # the family's step takes the whole carried state pool and the
            # layer's rank, and hands back the pool with this layer's rows
            # updated where they lie (a kernel over the live slots, or an
            # update of every slot's rows); the engine writes the tail.  A
            # slot the step computes on though dead does no harm: states
            # never mix across slots, and its next prefill writes it whole
            x, ssm_pool, tail = family.recurrent_step(l, x, state, i, live, cfg, mesh)
            with jax.named_scope(family.recurrent_scopes[1]):
                return x, {"ssm": ssm_pool, "conv": state["conv"].at[i].set(tail)}

        x, kp, vp, state, load = _run_plan(
            kinds, plain_layers, x, kp, vp, state, attention, recurrent,
            lambda l, x: family.ffn(l, x, live, cfg),
        )
    with jax.named_scope("atpu_serve_head"):
        logits = family.finalize(g, x, cfg)  # (slots, V)
        if temperature == 0.0:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            rngs_out = rngs
        else:
            # per-slot streams: a request's sampled tokens depend only on its
            # own key, never on which neighbours share the batch or finish
            def sample_one(key_data, lg):
                nk, sk = jax.random.split(key_data)
                return nk, jax.random.categorical(sk, lg / temperature).astype(jnp.int32)

            rngs_out, nxt = jax.vmap(sample_one)(rngs, logits)
    if kinds is None:
        return kp.reshape(pool_shape), vp.reshape(pool_shape), nxt, rngs_out
    return kp.reshape(pool_shape), vp.reshape(pool_shape), nxt, rngs_out, state, load


@partial(
    jax.jit,
    static_argnames=("family", "cfg", "qbits", "temperature", "mesh"),
    donate_argnums=(0, 1, 8),
)
def _decode_jit(
    k_pool,
    v_pool,
    g,
    layers,
    block_tables,
    positions,
    tokens,
    rngs,
    state=None,
    *,
    family: DecoderFamily,
    cfg,
    qbits: int,
    temperature: float,
    mesh=None,
):
    """The classic single-token program — ``_decode_body`` jitted with the
    signature, donation split and outputs the service has always pinned.
    ``decode_steps=1`` dispatches THIS program, not a length-1 loop: a
    degenerate ``_decode_n_jit`` returns extra outputs that alias each other
    (``positions + 1``, the token block AND the trailing token both being
    ``nxt``), a pattern that intermittently corrupted token streams on
    XLA:CPU (see the module docstring's aliasing note)."""
    return _decode_body(
        k_pool, v_pool, g, layers, block_tables, positions, tokens, rngs, state,
        family=family, cfg=cfg, qbits=qbits, temperature=temperature, mesh=mesh,
    )


@partial(
    jax.jit,
    static_argnames=("family", "cfg", "qbits", "temperature", "decode_steps", "mesh"),
    donate_argnums=(0, 1),
)
def _decode_n_jit(
    k_pool,
    v_pool,
    g,
    layers,
    block_tables,  # (slots, blocks_per_slot) int32 — NOT donated (reused)
    positions,  # (slots,) int32 — advanced in-program, returned
    tokens,  # (slots,) int32 — each sampled token fed back in-program
    rngs,  # (slots, 2) uint32 — per-slot streams, split in-program
    *,
    family: DecoderFamily,
    cfg,
    qbits: int,
    temperature: float,
    decode_steps: int = 1,
    mesh=None,
):
    """``decode_steps`` micro-steps of ``_decode_body`` in one captured
    program: the sampled token feeds the next embed and positions advance
    WITHOUT leaving the device.  Returns ``(k_pool, v_pool, tok_block,
    positions, tokens, rngs)`` where ``tok_block`` is ``(slots,
    decode_steps)`` int32 — one dispatch and one host sync per *n* tokens.

    ``decode_steps`` is static and >= 2 here (callers route 1 to the
    legacy ``_decode_jit`` — see its docstring for why the degenerate loop
    must not exist as a program): each distinct n is its own pinned
    program, riding the CompileWatcher signature and the serving AOT
    fingerprint — flipping it is a loud new program, never a silent
    steady-state recompile.

    Only the POOLS are donated.  positions/tokens/rngs are scan carries
    whose final values alias slices of the stacked ``tok_block`` output
    (``tokens`` out == ``tok_block[:, -1]``), and donating them tripped an
    allocation-dependent XLA:CPU aliasing corruption (module docstring) —
    they stay undonated, three tiny int arrays."""
    statics = dict(family=family, cfg=cfg, qbits=qbits, temperature=temperature, mesh=mesh)

    def micro(carry, _):
        kp, vp, pos, tok, rg = carry
        kp, vp, nxt, rg = _decode_body(
            kp, vp, g, layers, block_tables, pos, tok, rg, **statics
        )
        # the sampled token IS the next micro-step's input; its k/v will be
        # scattered at pos+1 — the host loop's feedback, now in-program
        return (kp, vp, pos + 1, nxt, rg), nxt

    (k_pool, v_pool, positions, tokens, rngs), toks = jax.lax.scan(
        micro, (k_pool, v_pool, positions, tokens, rngs), None,
        length=decode_steps,
    )
    # scan stacks along the leading (micro-step) axis; the scheduler wants
    # per-slot rows
    return k_pool, v_pool, jnp.moveaxis(toks, 0, 1), positions, tokens, rngs


class CompileWatcher:
    """Recompile forensics for the module-level jitted serving entries.

    The capture path's telemetry hooks live in ``CapturedStep``; the serving
    programs are plain ``jax.jit`` functions, so the watcher reconstructs
    the same signal from the jit cache: cache growth on a signature's FIRST
    call is warmup, growth on a SEEN signature is a steady-state recompile —
    counted, and emitted as a ``kind="serving"`` RecompileEvent through the
    telemetry hub when one is attached.  ``recompile_events == 0`` after
    warmup is the serving acceptance contract (ISSUE 7; tests, ``chip_smoke.py``).
    """

    def __init__(self, hub=None):
        self.hub = hub
        self.compiles_total = 0
        self.recompile_events = 0
        self._seen: set = set()
        self._calls = 0

    def note_build(self, label: str, signature, seen: Optional[bool] = None) -> None:
        """Count one program build.  A build on an already-seen signature is
        a steady-state recompile (counted + emitted as forensics).  Shared
        by the jit-cache diff path below and the AOT executable-cache path
        (native/aot_cache.py), so both dispatch routes keep one contract —
        including the warmed-from-disk case, where ``seen`` is passed
        explicitly because the watcher never saw the cold build."""
        if seen is None:
            seen = signature in self._seen
        self.compiles_total += 1
        if seen:
            self.recompile_events += 1
            if self.hub is not None:
                from ..telemetry import RecompileEvent, key_id

                self.hub.record_recompile(
                    RecompileEvent(
                        step=self._calls,
                        key=key_id(signature),
                        prev_key=key_id(signature),
                        causes=[
                            f"serving {label} compiled a new program for an "
                            f"already-warm signature {signature!r} — the "
                            "zero-recompile steady-state contract is broken"
                        ],
                        kind="serving",
                    )
                )
        self._seen.add(signature)

    def call(self, label: str, signature, jit_fn, *args, **kwargs):
        self._calls += 1
        seen = signature in self._seen
        before = jit_fn._cache_size()
        out = jit_fn(*args, **kwargs)
        if jit_fn._cache_size() > before:
            self.note_build(label, signature, seen=seen)
        else:
            self._seen.add(signature)
        return out


def _dispatch(label: str, sig, jit_fn, args, statics, watcher, aot):
    """The one way a serving program is called.  On a signature's first call
    the program is also entered in the scope registry (telemetry/profiler.py:
    the jit function, its statics and the arguments' shapes, dtypes and
    shardings — no buffer — from which its HLO text can be had again later),
    and that call, the one that compiles, runs with the scopes in the
    persistent-cache key."""
    from ..telemetry import profiler

    key = (sig, *statics.values())  # sig leaves the model out; cfg is a static
    first = not profiler.program_registered(key)
    if first:
        profiler.register_program(
            f"jit_{jit_fn.__name__}", profiler.relowered_text_fn(jit_fn, args, statics),
            key=key,
        )
    with profiler.scopes_in_cache_key() if first else contextlib.nullcontext():
        if aot is not None:
            return aot.call(label, sig, jit_fn, args, statics, watcher=watcher)
        if watcher is None:
            return jit_fn(*args, **statics)
        return watcher.call(label, sig, jit_fn, *args, **statics)


def _pool_mesh(pool):
    """The mesh ``pool`` is committed to, if it has several devices (the
    service commits its pools replicated on a sharded model's mesh), else
    None.  The decode programs take it as a static: their attention is a
    Mosaic kernel, which on several devices has to sit inside ``shard_map``
    (``native/kernels/paged_attention.py``), and a traced pool no longer says
    where it lives."""
    mesh = getattr(pool.sharding, "mesh", None)
    return mesh if mesh is not None and mesh.size > 1 else None


def run_prefill(k_pool, v_pool, g, layers, padded_ids, block_row, prompt_len,
                rng, *, family, cfg, qbits, temperature,
                watcher: Optional[CompileWatcher] = None, aot=None,
                slot=None, state=None):
    """One request's bucketed prefill; see ``_prefill_jit``.  ``padded_ids``
    must already be bucket-padded (``kv_blocks.bucket_length``) — raw
    request-length shapes here compile one program per distinct length
    (graftlint: recompile-hazard serving contract).  ``aot`` (an
    :class:`~..native.aot_cache.AOTServingPrograms`) replaces the jit
    dispatch with the persistent-executable path: signature hits run the
    deserialized program, misses compile explicitly and store it.

    A mixed layer plan (``models.generation.layer_plan``) also takes the
    ``state`` pool and the ``slot`` this prefill writes in it, and returns
    ``(k_pool, v_pool, tok, rng, state, load)``."""
    args = (k_pool, v_pool, g, layers, padded_ids, block_row, prompt_len, rng)
    if state is not None:
        args += (jnp.asarray(slot, jnp.int32), state)
    statics = dict(family=family, cfg=cfg, qbits=qbits, temperature=temperature)
    sig = ("prefill", padded_ids.shape[1], qbits, float(temperature))
    return _dispatch("prefill", sig, _prefill_jit, args, statics, watcher, aot)


def run_decode(k_pool, v_pool, g, layers, block_tables, positions, tokens,
               rngs, *, family, cfg, qbits, temperature,
               watcher: Optional[CompileWatcher] = None, aot=None,
               state=None):
    """One token for the whole slot batch; see ``_decode_jit``.  The
    ``decode_steps=1`` (default) dispatch path.

    A mixed layer plan also takes the ``state`` pool and returns
    ``(k_pool, v_pool, tokens, rngs, state, load)``."""
    args = (k_pool, v_pool, g, layers, block_tables, positions, tokens, rngs)
    if state is not None:
        args += (state,)
    statics = dict(family=family, cfg=cfg, qbits=qbits, temperature=temperature,
                   mesh=_pool_mesh(k_pool))
    sig = ("decode", block_tables.shape, qbits, float(temperature))
    return _dispatch("decode", sig, _decode_jit, args, statics, watcher, aot)


def run_decode_n(k_pool, v_pool, g, layers, block_tables, positions, tokens,
                 rngs, *, family, cfg, qbits, temperature, decode_steps=1,
                 watcher: Optional[CompileWatcher] = None, aot=None):
    """``decode_steps`` tokens for the whole slot batch in one dispatch;
    see ``_decode_n_jit``.  Returns ``(k_pool, v_pool, tok_block,
    positions, tokens, rngs)`` with ``tok_block`` of shape ``(slots,
    decode_steps)``.

    ``decode_steps=1`` delegates to :func:`run_decode` (the legacy
    single-token program — see ``_decode_jit`` for why a length-1 loop
    variant must not exist) and adapts its outputs to the uniform 6-tuple
    with two tiny eager device ops; the scheduler calls ``run_decode``
    directly on that path instead, skipping the adaptation.

    ``decode_steps`` is a STATIC compile-mode choice, so it rides the
    watcher/AOT signature: flipping it is a new program, never a silent
    steady-state recompile."""
    decode_steps = int(decode_steps)
    if decode_steps == 1:
        k_pool, v_pool, nxt, rngs = run_decode(
            k_pool, v_pool, g, layers, block_tables, positions, tokens, rngs,
            family=family, cfg=cfg, qbits=qbits, temperature=temperature,
            watcher=watcher, aot=aot,
        )
        return k_pool, v_pool, nxt[:, None], positions + 1, nxt, rngs
    args = (k_pool, v_pool, g, layers, block_tables, positions, tokens, rngs)
    statics = dict(family=family, cfg=cfg, qbits=qbits, temperature=temperature,
                   decode_steps=decode_steps, mesh=_pool_mesh(k_pool))
    sig = ("decode", block_tables.shape, qbits, float(temperature), decode_steps)
    return _dispatch("decode", sig, _decode_n_jit, args, statics, watcher, aot)
