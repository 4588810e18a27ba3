"""Decode service: continuous batching + paged KV cache (docs/serving.md).

The acceptance contract (ISSUE 7): mixed-length concurrent requests through
the service produce greedy tokens identical to single-request ``generate()``,
with zero recompile events after warmup, FIFO admission, immediate eviction,
and leak-free block accounting — all on the CPU mesh.
"""

import numpy as np
import pytest

import accelerate_tpu.nn as nn
from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
from accelerate_tpu.serving import (
    BlockPool,
    DecodeService,
    ServingConfig,
    blocks_for_request,
    bucket_length,
)


@pytest.fixture(scope="module")
def tiny_model():
    nn.manual_seed(0)
    model = GPTLMHeadModel(GPTConfig.tiny())
    model.eval()
    return model


def _prompts(lengths, vocab=1024, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,), dtype=np.int32) for n in lengths]


# ---------------------------------------------------------------------------
# kv_blocks: allocator + bucketing
# ---------------------------------------------------------------------------

def test_bucket_length_rounds_up_and_clamps():
    assert bucket_length(1, 16) == 16
    assert bucket_length(16, 16) == 16
    assert bucket_length(17, 16) == 32
    assert bucket_length(60, 16, cap=64) == 64
    # never below n, even past the cap
    assert bucket_length(70, 16, cap=64) == 70
    with pytest.raises(ValueError):
        bucket_length(0, 16)


def test_block_pool_alloc_free_no_leaks():
    pool = BlockPool(num_blocks=9, block_size=4, max_slots=2, blocks_per_slot=4)
    assert pool.usable_blocks == 8
    a = pool.alloc(0, 3)
    b = pool.alloc(1, 4)
    assert len(set(a) | set(b)) == 7 and 0 not in a + b
    assert pool.free_blocks == 1
    assert not pool.can_alloc(2)
    pool.check_no_leaks()
    assert pool.free_slot(0) == 3
    assert pool.free_blocks == 4
    # freed blocks are reusable; double-free is a no-op
    assert pool.free_slot(0) == 0
    c = pool.alloc(0, 4)
    assert 0 not in c
    pool.check_no_leaks()
    pool.free_slot(0)
    pool.free_slot(1)
    assert pool.free_blocks == pool.usable_blocks
    pool.check_no_leaks()


def test_block_pool_rejects_oversized_and_double_alloc():
    pool = BlockPool(num_blocks=9, block_size=4, max_slots=2, blocks_per_slot=4)
    with pytest.raises(ValueError, match="blocks_per_slot"):
        pool.alloc(0, 5)
    pool.alloc(0, 2)
    with pytest.raises(ValueError, match="already holds"):
        pool.alloc(0, 1)


# ---------------------------------------------------------------------------
# the acceptance contract: continuous batching == single-request generate()
# ---------------------------------------------------------------------------

def test_continuous_batch_matches_single_request_generate(tiny_model):
    """8 concurrent mixed-length requests with staggered arrivals: every
    request's greedy tokens are identical to a lone generate() of the same
    prompt, and the steady state is zero recompiles (ISSUE 7 acceptance)."""
    service = DecodeService(
        tiny_model, ServingConfig(max_slots=4, block_size=16, prompt_bucket=16)
    )
    lengths = [3, 9, 17, 30, 5, 24, 12, 40]
    budgets = [6, 4, 8, 3, 7, 5, 6, 4]
    prompts = _prompts(lengths)
    # stagger arrivals: two submissions per step while earlier requests are
    # mid-decode — sequences genuinely join an in-flight batch
    rids, pending = [], list(zip(prompts, budgets))
    while pending or service.has_work:
        for _ in range(2):
            if pending:
                p, b = pending.pop(0)
                rids.append(service.submit(p, max_new_tokens=b))
        service.step()
    for rid, p, b in zip(rids, prompts, budgets):
        want = np.asarray(tiny_model.generate(p[None], max_new_tokens=b))[0]
        got = service.results[rid].output_ids
        np.testing.assert_array_equal(got, want, err_msg=f"request {rid}")
    # eviction returned every block
    service.pool.check_no_leaks()
    assert service.pool.free_blocks == service.pool.usable_blocks


def _contiguous_cache(model, prompt, n_decode):
    """What ``generate()`` keeps for one request, replayed eagerly: its
    contiguous ``(L, n_kv, positions, d)`` k/v cache after the prefill and
    ``n_decode`` greedy decode steps (``_generate_jit``'s own algorithm:
    prefill the prompt, then one token a step written at its position), and
    the tokens it sampled on the way."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.generation import (
        _dequant_layer, cached_attention, stacked_params_for_mode,
    )

    spec = model._decoder_spec()
    family, cfg = spec.family, spec.cfg
    g, (plain, quant, scales) = stacked_params_for_mode(model, 0, spec.stack)
    n_layers = next(iter(plain.values())).shape[0]
    layer = lambda i, x: _dequant_layer(  # noqa: E731
        *jax.tree_util.tree_map(lambda a: a[i], (plain, quant, scales)), 0, x.dtype
    )
    p_len = len(prompt)
    positions = jnp.arange(p_len)
    x = family.embed(g, jnp.asarray(prompt)[None], positions, cfg)
    k_cache, v_cache = [], []
    pad = [(0, 0), (0, 0), (0, n_decode), (0, 0)]
    for i in range(n_layers):
        l = layer(i, x)
        q, k, v = family.attn_in(l, x, positions, cfg)
        x = family.attn_out(l, x, cached_attention(q, k, v, positions, cfg), cfg)
        k_cache.append(jnp.pad(k, pad))
        v_cache.append(jnp.pad(v, pad))
    tokens = [int(jnp.argmax(family.finalize(g, x[:, -1:], cfg), axis=-1)[0])]
    for step in range(n_decode):
        q_pos = jnp.asarray([p_len + step])
        x = family.embed(g, jnp.asarray([[tokens[-1]]], jnp.int32), q_pos, cfg)
        for i in range(n_layers):
            l = layer(i, x)
            q, k, v = family.attn_in(l, x, q_pos, cfg)
            k_cache[i] = jax.lax.dynamic_update_slice(k_cache[i], k, (0, 0, p_len + step, 0))
            v_cache[i] = jax.lax.dynamic_update_slice(v_cache[i], v, (0, 0, p_len + step, 0))
            x = family.attn_out(l, x, cached_attention(q, k_cache[i], v_cache[i], q_pos, cfg), cfg)
        tokens.append(int(jnp.argmax(family.finalize(g, x, cfg), axis=-1)[0]))
    stacked = lambda cache: np.stack([np.asarray(c[0]) for c in cache])  # noqa: E731
    return stacked(k_cache), stacked(v_cache), tokens


def _tiny_llama():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig.tiny()).eval()  # 4 heads over 2 kv heads


@pytest.mark.parametrize("make_model", [lambda: GPTLMHeadModel(GPTConfig.tiny()).eval(), _tiny_llama],
                         ids=["mha", "gqa"])
def test_pool_pages_hold_generates_contiguous_cache(make_model):
    """The pool is ``(L, NB, bs, lanes)`` — a page is one ``[bs, lanes]`` slab,
    a token's ``n_kv·d`` first and zeros up to whole 128-lane tiles
    (``page_lanes``), the block index a major dimension — and after a prefill
    and N decode steps the request's pages, read through its block table,
    hold position for position the k/v of ``generate()``'s contiguous cache
    (a page off by one block or one row would differ in the first digit)."""
    from accelerate_tpu.serving.kv_blocks import page_lanes

    nn.manual_seed(0)
    model = make_model()
    dcfg = model._decoder_spec().cfg
    block, n_decode = 4, 7
    service = DecodeService(model, ServingConfig(max_slots=2, block_size=block, prompt_bucket=8))
    n_layers = next(iter(service._layers[0].values())).shape[0]
    width = dcfg.n_kv_head * dcfg.head_dim
    assert page_lanes(dcfg.n_kv_head, dcfg.head_dim) == 128 >= width  # gqa: 64 of 128
    assert service._k_pool.shape == service._v_pool.shape == (
        n_layers, service.pool.num_blocks, block, 128
    )
    # a short request that leaves and a long one that stays, so the request
    # under test gets the first one's blocks, then blocks past the second's:
    # its pages are neither in order from 1 nor next to each other
    service.submit(_prompts([3], seed=3)[0], max_new_tokens=2)
    service.submit(_prompts([5], seed=5)[0], max_new_tokens=40)
    service.step()
    service.step()
    prompt = _prompts([11], seed=4)[0]
    rid = service.submit(prompt, max_new_tokens=n_decode + 5)
    for _ in range(n_decode):  # the first step admits, prefills AND decodes once
        service.step()
    slot = next(i for i, r in enumerate(service._slot_req) if r is not None and r.rid == rid)
    row = service.pool.row(slot)
    assert any(b - a != 1 for a, b in zip(row, row[1:])), row  # the table is doing work
    held = len(prompt) + n_decode  # positions written so far
    assert int(service._positions[slot]) == held

    k_want, v_want, tokens = _contiguous_cache(model, prompt, n_decode)
    want_ids = np.asarray(model.generate(prompt[None], max_new_tokens=n_decode + 1))[0]
    np.testing.assert_array_equal(tokens, want_ids[len(prompt):])  # the replay IS generate()
    for pool, want in ((service._k_pool, k_want), (service._v_pool, v_want)):
        pool = np.asarray(pool)
        assert not pool[..., width:].any()  # the pad lanes stay zero
        for page in range(-(-held // block)):
            lo, hi = page * block, min((page + 1) * block, held)
            got = pool[:, row[page], : hi - lo, :width].reshape(
                n_layers, hi - lo, dcfg.n_kv_head, dcfg.head_dim
            )
            # to round-off: the replay runs eagerly, the service's programs fused
            np.testing.assert_allclose(
                got.transpose(0, 2, 1, 3), want[:, :, lo:hi], rtol=1e-5, atol=1e-5,
                err_msg=f"page {page} (block {row[page]})",
            )
    service.run()
    np.testing.assert_array_equal(
        service.results[rid].output_ids,
        np.asarray(model.generate(prompt[None], max_new_tokens=n_decode + 5))[0],
    )
    service.pool.check_no_leaks()


def _tap_logits(service) -> list:
    """Every ``(rows, V)`` logits array the service's programs compute from
    here on, float32, in order: its family's ``finalize`` with a host callback
    behind it."""
    import dataclasses

    import jax

    seen, family = [], service.spec.family

    def finalize(g, x, cfg):
        logits = family.finalize(g, x, cfg)
        jax.debug.callback(lambda a: seen.append(np.asarray(a, np.float32)), logits, ordered=True)
        return logits

    service.spec = dataclasses.replace(
        service.spec, family=dataclasses.replace(family, finalize=finalize)
    )
    return seen


def test_decode_logits_agree_with_a_full_forward_to_float32_summation_order(tiny_model):
    """What holds between the two engines now that decode attention sums in
    chunks of pages: the same greedy tokens as ``generate()`` on this model,
    and decode logits equal to a full forward's at the same positions to
    float32 rounding — not bitwise.  Requests long enough to cross a chunk's
    edge (128 tokens), batched with short ones."""
    from accelerate_tpu.nn import Tensor, no_grad

    service = DecodeService(tiny_model, ServingConfig(max_slots=3, block_size=16, prompt_bucket=16))
    seen = _tap_logits(service)
    prompts, new = _prompts([120, 7, 60], seed=11), 12
    rids = [service.submit(p, max_new_tokens=new) for p in prompts]
    service.step()  # admits all three (a prefill each), then decodes once
    service.run()
    decodes = [lg for lg in seen if lg.shape[0] == 3]  # (slots, V): the decode steps
    assert len(decodes) == new - 1
    slot_of = {rid: slot for slot, rid in enumerate(rids)}  # admitted in order into slots 0, 1, 2
    for rid, prompt in zip(rids, prompts):
        got_ids = service.results[rid].output_ids
        np.testing.assert_array_equal(
            got_ids, np.asarray(tiny_model.generate(prompt[None], max_new_tokens=new))[0]
        )
        with no_grad():
            full = np.asarray(tiny_model(Tensor(got_ids[None]))["logits"].data)[0]
        # decode step j fed the token at position len(prompt) + j
        want = full[len(prompt): len(prompt) + new - 1]
        got = np.stack([lg[slot_of[rid]] for lg in decodes])
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("decode_steps", [1, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_width_off_the_lanes_serves_what_generate_does(dtype, decode_steps):
    """GPT-2 at a width that is not a multiple of 128, with heads of 64 (320 =
    5 x 64, as GPT-2-XL's 1600 = 25 x 64): the decode family's embed is the
    one-hot product over the table as it lies and its qkv product stays
    two-dimensional until its result is cut (docs/serving.md §weights are read
    where they lie).  Served greedy tokens equal ``generate()``'s, the decode
    logits a full forward's to float32 summation order (to bfloat16's rounding
    in bfloat16), 0 recompiles."""
    import jax.numpy as jnp

    from accelerate_tpu.nn import Tensor, no_grad

    # seed 2: in bfloat16 the two engines' logits differ by one rounding step
    # (0.016) and this model's narrowest top-2 gap is 0.10; of 14 seeds tried
    # one holds an exact tie, which either engine may break its own way
    nn.manual_seed(2)
    model = GPTLMHeadModel(
        GPTConfig(vocab_size=512, n_positions=256, n_embd=320, n_layer=2, n_head=5)
    ).eval()
    for p in model.parameters():
        p.data = p.data.astype(jnp.dtype(dtype))
    service = DecodeService(model, ServingConfig(
        max_slots=3, block_size=16, prompt_bucket=16, decode_steps=decode_steps))
    assert service.spec.cfg.head_dim == 64
    seen = _tap_logits(service)
    prompts, new = _prompts([120, 7, 60], vocab=512, seed=5), 12
    rids = [service.submit(p, max_new_tokens=new) for p in prompts]
    service.step()  # admits all three (a prefill each), then decodes
    service.run()
    assert service.recompile_events == 0
    service.pool.check_no_leaks()
    decodes = [lg for lg in seen if lg.shape[0] == 3][: new - 1]  # micro-steps past the budget aside
    assert len(decodes) == new - 1
    tol = 2e-5 if dtype == "float32" else 6e-2
    for slot, (rid, prompt) in enumerate(zip(rids, prompts)):  # admitted in order into slots 0, 1, 2
        got_ids = service.results[rid].output_ids
        np.testing.assert_array_equal(
            got_ids, np.asarray(model.generate(prompt[None], max_new_tokens=new))[0]
        )
        with no_grad():
            full = np.asarray(model(Tensor(got_ids[None]))["logits"].data, np.float32)[0]
        # decode step j fed the token at position len(prompt) + j
        want = full[len(prompt): len(prompt) + new - 1]
        np.testing.assert_allclose(np.stack([lg[slot] for lg in decodes]), want, rtol=tol, atol=tol)


def test_kv_pages_walked_over_tabled_reads_what_the_lengths_imply(tiny_model):
    """``stats["kv_pages_walked"]`` counts the pages decode attention read —
    each decoding slot's own length, a token at a time — and
    ``["kv_pages_tabled"]`` the pages those slots' table rows span
    (``blocks_per_slot`` each: what the gather path attended over): both
    follow from the submitted lengths alone, and the ring's step span carries
    each step's pair."""
    from accelerate_tpu.telemetry import flightrec

    block = 4
    service = DecodeService(tiny_model, ServingConfig(max_slots=2, block_size=block, prompt_bucket=8))
    lengths, budgets = [5, 11], [9, 3]
    for p, b in zip(_prompts(lengths, seed=12), budgets):
        service.submit(p, max_new_tokens=b)
    service.run()
    walked = tabled = 0
    for n, b in zip(lengths, budgets):
        # the prefill samples token 1; decode step j feeds the token at position n + j
        fed = np.arange(n, n + b - 1)
        walked += int((fed // block + 1).sum())
        tabled += (b - 1) * service._tables.shape[1]
    assert (service.stats["kv_pages_walked"], service.stats["kv_pages_tabled"]) == (walked, tabled)
    assert 0 < walked < tabled
    steps = [e for e in flightrec.recorder().snapshot() if e["kind"] == "atpu/serve/step"]
    mine = steps[-service.stats["steps"]:]
    assert sum(e.get("kv_pages_walked", 0) for e in mine) == walked
    assert sum(e.get("kv_pages_tabled", 0) for e in mine) == tabled


def test_zero_recompiles_in_steady_state(tiny_model):
    """After one decode build + one prefill build per prompt bucket, every
    further call replays — the CompileWatcher forensics count stays 0."""
    from accelerate_tpu.serving import engine

    engine._prefill_jit.clear_cache()
    engine._decode_jit.clear_cache()
    service = DecodeService(
        tiny_model, ServingConfig(max_slots=4, block_size=16, prompt_bucket=16)
    )
    # warmup: both buckets + the decode program
    for n in (4, 20):
        service.submit(np.ones(n, np.int32), max_new_tokens=3)
    service.run()
    warm = service.watcher.compiles_total
    assert warm >= 3  # 2 prefill buckets + 1 decode program
    # a second wave over the same buckets, different lengths/budgets
    for p, b in zip(_prompts([5, 9, 17, 31, 2, 26], seed=1), [4, 2, 5, 3, 6, 2]):
        service.submit(p, max_new_tokens=b)
    service.run()
    assert service.watcher.compiles_total == warm
    assert service.recompile_events == 0


def test_zero_recompiles_with_prepared_model():
    """Regression: a PREPARED model's params carry a NamedSharding, and the
    first captured call used to return the (uncommitted, single-device)
    pools re-committed onto that mesh — flipping the input sharding and
    silently recompiling every program on its second call.  The service now
    commits pools/rng streams replicated on the params' mesh up front."""
    from accelerate_tpu import Accelerator

    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator()
    model = acc.prepare(GPTLMHeadModel(GPTConfig.tiny()))
    model.eval()
    service = DecodeService(
        model, ServingConfig(max_slots=4, block_size=16, prompt_bucket=16)
    )
    for n in (4, 20):
        service.submit(np.ones(n, np.int32), max_new_tokens=3)
    service.run()
    warm = service.watcher.compiles_total
    for p, b in zip(_prompts([5, 17, 9, 30], seed=9), [4, 6, 3, 5]):
        service.submit(p, max_new_tokens=b)
    service.run()
    assert service.watcher.compiles_total == warm
    assert service.recompile_events == 0


def test_admission_fifo_and_immediate_eviction(tiny_model):
    """Admission is FIFO; a finished sequence frees its slot immediately and
    the next queued request takes it while others are still mid-decode."""
    service = DecodeService(
        tiny_model, ServingConfig(max_slots=2, block_size=16, prompt_bucket=16)
    )
    prompts = _prompts([4, 5, 6, 7], seed=2)
    # r0 finishes after 3 tokens, r1 is long; r2/r3 wait in the queue
    r0 = service.submit(prompts[0], max_new_tokens=3)
    r1 = service.submit(prompts[1], max_new_tokens=12)
    r2 = service.submit(prompts[2], max_new_tokens=3)
    r3 = service.submit(prompts[3], max_new_tokens=3)
    service.step()  # admits r0 + r1 (FIFO), decodes one token
    assert [r.rid for r in service._slot_req if r is not None] == [r0, r1]
    assert [r.rid for r in service._queue] == [r2, r3]
    done = service.step()  # r0 hits its budget -> evicted this step
    assert [r.rid for r in done] == [r0]
    service.step()  # r2 takes r0's slot NEXT step, r1 still running
    assert r2 in [r.rid for r in service._slot_req if r is not None]
    assert service.results.keys() >= {r0}
    service.run()
    # completion order respects arrival for equal budgets: r2 before r3
    assert list(service.results) == sorted(
        service.results, key=lambda rid: service.results[rid].done_t
    )
    assert service.results[r2].done_t < service.results[r3].done_t
    assert (r1 in service.results) and (r3 in service.results)
    service.pool.check_no_leaks()


def test_queue_backpressure_on_block_exhaustion(tiny_model):
    """An undersized pool gates admission (requests wait) instead of
    failing: with blocks for ~one max request, the service degrades to
    near-serial but still completes everything."""
    service = DecodeService(
        tiny_model,
        ServingConfig(
            max_slots=4, block_size=16, prompt_bucket=16, num_blocks=5
        ),
    )
    prompts = _prompts([17, 20, 25], seed=3)
    rids = [service.submit(p, max_new_tokens=4) for p in prompts]
    service.step()
    # only the head fit (needs 2 blocks of the 4 usable... the second also
    # fits; the third waits)
    assert service.active_slots <= 2 and len(service._queue) >= 1
    service.run()
    for rid, p in zip(rids, prompts):
        want = np.asarray(tiny_model.generate(p[None], max_new_tokens=4))[0]
        np.testing.assert_array_equal(service.results[rid].output_ids, want)
    service.pool.check_no_leaks()


def test_submit_validation(tiny_model):
    service = DecodeService(
        tiny_model, ServingConfig(max_slots=2, block_size=16, prompt_bucket=16)
    )
    with pytest.raises(ValueError, match="capacity"):
        service.submit(np.ones(250, np.int32), max_new_tokens=20)
    with pytest.raises(ValueError, match="max_new_tokens"):
        service.submit(np.ones(4, np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="empty"):
        service.submit(np.zeros(0, np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="multiple"):
        DecodeService(
            tiny_model, ServingConfig(block_size=16, prompt_bucket=24)
        )


def test_per_request_stop_token(tiny_model):
    """A request with eos stops the step its sampled token hits it (the eos
    itself is emitted, matching generate()); others run to budget."""
    prompts = _prompts([6, 8], seed=4)
    # the greedy continuation's 3rd token plays the "eos"; it may repeat
    # earlier in the stream, so the expected stop is its FIRST occurrence
    p_len = len(prompts[0])
    ref = np.asarray(tiny_model.generate(prompts[0][None], max_new_tokens=8))[0]
    eos = int(ref[p_len + 2])
    first_hit = int(np.argmax(ref[p_len:] == eos))
    service = DecodeService(
        tiny_model, ServingConfig(max_slots=2, block_size=16, prompt_bucket=16)
    )
    r0 = service.submit(prompts[0], max_new_tokens=8, eos_token_id=eos)
    r1 = service.submit(prompts[1], max_new_tokens=8)
    service.run()
    got = service.results[r0].output_ids
    # stopped at the stop token, which is itself emitted
    assert got.shape[0] == p_len + first_hit + 1 and got[-1] == eos
    np.testing.assert_array_equal(got, ref[: len(got)])
    want1 = np.asarray(tiny_model.generate(prompts[1][None], max_new_tokens=8))[0]
    np.testing.assert_array_equal(service.results[r1].output_ids, want1)
    service.pool.check_no_leaks()


def test_quantized_mode_composes(tiny_model):
    """int8 weight mode rides the SAME stacked-param cache as generate():
    serving outputs match quantized single-request decode token for token."""
    service = DecodeService(
        tiny_model,
        ServingConfig(
            max_slots=4, block_size=16, prompt_bucket=16, quantize_weights=8
        ),
    )
    prompts = _prompts([5, 11, 19], seed=5)
    rids = [service.submit(p, max_new_tokens=5) for p in prompts]
    service.run()
    for rid, p in zip(rids, prompts):
        want = np.asarray(
            tiny_model.generate(p[None], max_new_tokens=5, quantize_weights=8)
        )[0]
        np.testing.assert_array_equal(service.results[rid].output_ids, want)
    # both modes live side by side in the per-model stack cache
    assert set(tiny_model._generation_param_cache[1]) >= {8}


@pytest.mark.parametrize("decode_steps", [1, 8])
def test_serving_telemetry_records(tiny_model, decode_steps):
    """With a hub attached, every step emits a kind='serving' occupancy
    record and every completion a TTFT/TPOT record, on the per-token path
    and on the device-resident block loop alike; the JSONL dump carries
    them (docs/telemetry.md schema)."""
    from accelerate_tpu.telemetry import Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryKwargs

    hub = Telemetry(TelemetryKwargs(enabled=True))
    service = DecodeService(
        tiny_model,
        ServingConfig(max_slots=2, block_size=16, prompt_bucket=16, decode_steps=decode_steps),
        telemetry=hub,
    )
    rids = [service.submit(p, max_new_tokens=3) for p in _prompts([4, 7, 9], seed=6)]
    service.run()
    records = [r for r in hub.all_records() if r.get("kind") == "serving"]
    steps = [r for r in records if r["event"] == "step"]
    completes = [r for r in records if r["event"] == "complete"]
    assert steps and all(
        0.0 <= r["occupancy"] <= 1.0 and "queue_depth" in r for r in steps
    )
    assert {r["rid"] for r in completes} == set(rids)
    assert all(r["ttft_ms"] is not None and r["ttft_ms"] >= 0 for r in completes)
    # multi-token requests report a per-token latency
    assert all(r["tpot_ms"] is not None for r in completes if r["new_tokens"] > 1)
    # occupancy statistic matches the recorded stream
    assert service.mean_batch_occupancy == pytest.approx(
        sum(r["occupancy"] for r in steps) / len(steps)
    )


def test_one_token_request_completes_at_admission(tiny_model):
    """max_new_tokens=1 finishes inside _admit (prefill samples the only
    token) and never occupies a decode slot."""
    service = DecodeService(
        tiny_model, ServingConfig(max_slots=2, block_size=16, prompt_bucket=16)
    )
    p = _prompts([6], seed=7)[0]
    rid = service.submit(p, max_new_tokens=1)
    done = service.step()
    assert [r.rid for r in done] == [rid]
    assert service.active_slots == 0
    want = np.asarray(tiny_model.generate(p[None], max_new_tokens=1))[0]
    np.testing.assert_array_equal(service.results[rid].output_ids, want)
    service.pool.check_no_leaks()


def test_result_retention_is_bounded(tiny_model):
    """A long-running service must not grow host memory with its request
    history: results retains the newest max_retained_results, and
    pop_result is the streaming-consumer take-and-drop API."""
    service = DecodeService(
        tiny_model,
        ServingConfig(
            max_slots=2, block_size=16, prompt_bucket=16,
            max_retained_results=2,
        ),
    )
    rids = [service.submit(p, max_new_tokens=2) for p in _prompts([4, 5, 6, 7], seed=10)]
    service.run()
    assert list(service.results) == rids[-2:]  # oldest two evicted
    taken = service.pop_result(rids[-1])
    assert taken is not None and taken.rid == rids[-1]
    assert service.pop_result(rids[-1]) is None
    assert service.pop_result(rids[0]) is None


def test_sampled_serving_is_slot_independent(tiny_model):
    """Per-slot RNG streams: a request's sampled tokens don't depend on
    which neighbours share the batch (solo run == batched run, same rid)."""
    def run(lengths, budgets, seed_rid_of_interest):
        service = DecodeService(
            tiny_model,
            ServingConfig(
                max_slots=4, block_size=16, prompt_bucket=16, temperature=1.0
            ),
        )
        prompts = _prompts(lengths, seed=8)
        rids = [service.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
        service.run()
        return service.results[rids[seed_rid_of_interest]].output_ids

    solo = run([9], [5], 0)
    crowded = run([9, 4, 17, 30], [5, 6, 4, 3], 0)
    np.testing.assert_array_equal(solo, crowded)


# ---------------------------------------------------------------------------
# device-resident multi-token decode (ISSUE 14): n-token captured blocks,
# on-device token feedback, one host sync per block
# ---------------------------------------------------------------------------

def _serve_all(service, prompts, budgets, per_step=2):
    """Staggered submission driver shared by the multi-token cases."""
    rids, pending = [], list(zip(prompts, budgets))
    while pending or service.has_work:
        for _ in range(per_step):
            if pending:
                p, b = pending.pop(0)
                rids.append(service.submit(p, max_new_tokens=b))
        service.step()
    return rids


def test_blocks_for_request_covers_overrun_horizon():
    """Reservation math: decode_steps=1 is the classic formula exactly;
    n>1 rounds the decode span up to whole n-blocks (the ≤ n-1 overrun
    writes stay inside the slot's own reservation) and clamps to the
    slot's table length for near-capacity requests."""
    # classic: ceil(max(bucket, p+new)/bs)
    assert blocks_for_request(3, 6, 16, 16) == 1
    assert blocks_for_request(3, 20, 16, 16) == 2
    assert blocks_for_request(30, 3, 32, 16) == 3
    # n=8: a 6-token budget runs 1 + ceil(5/8)*8 = 9 positions past p_len
    assert blocks_for_request(3, 6, 16, 16, decode_steps=8) == 1
    assert blocks_for_request(14, 6, 16, 16, decode_steps=8) == 2  # 14+9=23
    # max_new=1 never holds a decode slot: horizon is 1 at every n
    assert blocks_for_request(3, 1, 16, 16, decode_steps=8) == 1
    # clamp: the overrun horizon may round past the table — tail writes are
    # trash-block/clamped-in-slot safe, so never reserve past the table
    assert blocks_for_request(50, 14, 64, 16, decode_steps=8,
                              blocks_per_slot=4) == 4


def test_multi_token_matches_generate_and_n1(tiny_model):
    """The tentpole acceptance: n=8 greedy tokens are per-sequence
    BITWISE identical to single-request generate() AND to the n=1 path,
    under staggered admission landing at block boundaries mid-flight."""
    lengths = [3, 9, 17, 30, 5, 24, 12, 40]
    budgets = [6, 4, 8, 3, 7, 5, 6, 4]
    prompts = _prompts(lengths)
    outs = {}
    for n in (1, 8):
        service = DecodeService(
            tiny_model,
            ServingConfig(max_slots=4, block_size=16, prompt_bucket=16,
                          decode_steps=n),
        )
        rids = _serve_all(service, prompts, budgets)
        outs[n] = [service.results[rid].output_ids for rid in rids]
        service.pool.check_no_leaks()
        assert service.pool.free_blocks == service.pool.usable_blocks
        assert service.recompile_events == 0
    for p, b, got1, got8 in zip(prompts, budgets, outs[1], outs[8]):
        want = np.asarray(tiny_model.generate(p[None], max_new_tokens=b))[0]
        np.testing.assert_array_equal(got1, want)
        np.testing.assert_array_equal(got8, want)


def test_multi_token_mid_block_eos_masking(tiny_model):
    """A stop token landing MID-block finishes the request at that token:
    the block's overrun tail is discarded (never reaches the output), the
    eos itself is emitted, and the output equals the generate() prefix —
    while a slot-mate without eos runs to budget unperturbed."""
    prompts = _prompts([6, 8], seed=4)
    p_len = len(prompts[0])
    ref = np.asarray(tiny_model.generate(prompts[0][None], max_new_tokens=8))[0]
    eos = int(ref[p_len + 2])  # 3rd generated token plays the eos
    first_hit = int(np.argmax(ref[p_len:] == eos))
    service = DecodeService(
        tiny_model,
        ServingConfig(max_slots=2, block_size=16, prompt_bucket=16,
                      decode_steps=8),
    )
    r0 = service.submit(prompts[0], max_new_tokens=8, eos_token_id=eos)
    r1 = service.submit(prompts[1], max_new_tokens=8)
    service.run()
    got = service.results[r0].output_ids
    assert got.shape[0] == p_len + first_hit + 1 and got[-1] == eos
    np.testing.assert_array_equal(got, ref[: len(got)])
    want1 = np.asarray(tiny_model.generate(prompts[1][None], max_new_tokens=8))[0]
    np.testing.assert_array_equal(service.results[r1].output_ids, want1)
    service.pool.check_no_leaks()


def test_multi_token_overrun_keeps_pool_leak_free(tiny_model):
    """Budgets that are NOT multiples of n overrun the captured block by up
    to n-1 micro-steps on an UNDERSIZED pool: every overrun write lands in
    the finishing slot's own reservation (or the trash block), the pool
    drains leak-free, and outputs stay exact."""
    service = DecodeService(
        tiny_model,
        ServingConfig(max_slots=4, block_size=16, prompt_bucket=16,
                      num_blocks=7, decode_steps=8),
    )
    prompts = _prompts([17, 20, 25], seed=3)
    budgets = [4, 11, 6]  # none a multiple of 8
    rids = [
        service.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)
    ]
    service.run()
    for rid, p, b in zip(rids, prompts, budgets):
        want = np.asarray(tiny_model.generate(p[None], max_new_tokens=b))[0]
        np.testing.assert_array_equal(service.results[rid].output_ids, want)
    service.pool.check_no_leaks()
    assert service.pool.free_blocks == service.pool.usable_blocks


def test_multi_token_overrun_at_capacity_feeds_a_position_past_the_table(tiny_model):
    """prompt + max_new == the slot's capacity at n=8: 22 decode tokens run
    as 3 blocks = 24 micro-steps, so the last one feeds position 64 — past
    the whole 4-page table.  Its k/v write is dropped (no table column names
    a block for it), and the attention kernel walks the slot's own 4 pages
    and no table entry past its row (``paged_attention._kernel`` clamps the
    position: on the chip that entry would be a DMA address; the
    interpreter here would clamp the index and count the last page twice,
    which the kernel test catches).  Both slots overrun, the last one
    included; tokens equal ``generate()``'s, the pool drains leak-free."""
    service = DecodeService(
        tiny_model,
        ServingConfig(max_slots=2, block_size=16, prompt_bucket=16,
                      max_request_len=64, decode_steps=8),
    )
    assert service.capacity == 64
    prompts = _prompts([41, 41], seed=11)
    rids = [service.submit(p, max_new_tokens=23) for p in prompts]
    fed = []
    step = service._step

    def watched(about):
        fed.append(int(service._positions.max()))
        return step(about)

    service._step = watched
    service.run()
    assert max(fed) + 8 > service.capacity  # the last block starts at 57: 57 .. 64
    for rid, p in zip(rids, prompts):
        want = np.asarray(tiny_model.generate(p[None], max_new_tokens=23))[0]
        np.testing.assert_array_equal(service.results[rid].output_ids, want)
    service.pool.check_no_leaks()
    assert service.recompile_events == 0


def test_zero_recompiles_steady_state_multi_token(tiny_model):
    """The zero-recompile contract holds at n>1: one decode-block program +
    one prefill program per bucket at warmup, then pure replays — and the
    decode_steps flip itself is a NEW signature, never a steady-state
    recompile event."""
    from accelerate_tpu.serving import engine

    engine._prefill_jit.clear_cache()
    engine._decode_n_jit.clear_cache()
    service = DecodeService(
        tiny_model,
        ServingConfig(max_slots=4, block_size=16, prompt_bucket=16,
                      decode_steps=8),
    )
    for n in (4, 20):
        service.submit(np.ones(n, np.int32), max_new_tokens=3)
    service.run()
    warm = service.watcher.compiles_total
    assert warm >= 3  # 2 prefill buckets + 1 decode-block program
    for p, b in zip(_prompts([5, 9, 17, 31, 2, 26], seed=1), [4, 2, 5, 3, 6, 2]):
        service.submit(p, max_new_tokens=b)
    service.run()
    assert service.watcher.compiles_total == warm
    assert service.recompile_events == 0
    assert service.host_syncs_per_token < 0.5  # blocks, not per-token syncs


def test_decode_steps_default_off_and_env_wiring(tiny_model, monkeypatch):
    """decode_steps defaults to 1 (today's per-token path, byte-identical)
    and resolves from $ACCELERATE_SERVING_DECODE_STEPS; a malformed value
    warns and keeps the default; <1 is rejected at construction."""
    assert ServingConfig().decode_steps == 1
    monkeypatch.setenv("ACCELERATE_SERVING_DECODE_STEPS", "8")
    assert ServingConfig().decode_steps == 8
    monkeypatch.setenv("ACCELERATE_SERVING_DECODE_STEPS", "fast")
    assert ServingConfig().decode_steps == 1
    monkeypatch.delenv("ACCELERATE_SERVING_DECODE_STEPS")
    with pytest.raises(ValueError, match="decode_steps"):
        DecodeService(tiny_model, ServingConfig(decode_steps=0))
    # explicit config wins over env
    monkeypatch.setenv("ACCELERATE_SERVING_DECODE_STEPS", "4")
    service = DecodeService(
        tiny_model,
        ServingConfig(max_slots=2, block_size=16, prompt_bucket=16,
                      decode_steps=1),
    )
    p = _prompts([7], seed=11)[0]
    rid = service.submit(p, max_new_tokens=5)
    service.run()
    want = np.asarray(tiny_model.generate(p[None], max_new_tokens=5))[0]
    np.testing.assert_array_equal(service.results[rid].output_ids, want)
    # the per-token path syncs once per token
    assert service.host_syncs_per_token == 1.0


@pytest.mark.parametrize("decode_steps", [4, 8])
def test_steady_state_step_uploads_nothing(tiny_model, decode_steps):
    """Regression (ISSUE 14 satellite): DecodeService.step() used to
    re-upload tables/positions/tokens every step even with no admission.
    On the multi-token path the decode state is device-resident — a
    steady-state step performs ZERO host→device transfers, enforced with a
    hard jax transfer guard (any upload raises), and the service's own h2d
    counter agrees.  (decode_steps=1 deliberately keeps the legacy
    per-step uploads: identical input avals → identical compiled binary →
    the bitwise generate() parity contract stays anchored to the exact
    program the seed service always ran.)"""
    import jax

    service = DecodeService(
        tiny_model,
        ServingConfig(max_slots=2, block_size=16, prompt_bucket=16,
                      decode_steps=decode_steps),
    )
    prompts = _prompts([5, 9], seed=12)
    rids = [service.submit(p, max_new_tokens=30) for p in prompts]
    service.step()  # admission step: uploads happen here, by design
    uploads_admit = service.stats["h2d_uploads"]
    assert uploads_admit >= 1
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            service.step()
    assert service.stats["h2d_uploads"] == uploads_admit
    service.run()
    for rid, p in zip(rids, prompts):
        want = np.asarray(tiny_model.generate(p[None], max_new_tokens=30))[0]
        np.testing.assert_array_equal(service.results[rid].output_ids, want)


def test_multi_token_telemetry_and_metrics_counters(tiny_model):
    """The new serving counters (docs/telemetry.md): step records carry
    decode_steps/emitted, metrics() exposes host_syncs_per_token and the
    h2d upload counter, and at n=8 the sync ratio lands near 1/8."""
    from accelerate_tpu.telemetry import Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryKwargs

    hub = Telemetry(TelemetryKwargs(enabled=True))
    service = DecodeService(
        tiny_model,
        ServingConfig(max_slots=4, block_size=16, prompt_bucket=16,
                      decode_steps=8),
        telemetry=hub,
    )
    prompts = _prompts([4, 7, 9], seed=6)
    _serve_all(service, prompts, [9, 8, 9])
    steps = [
        r for r in hub.all_records()
        if r.get("kind") == "serving" and r.get("event") == "step"
    ]
    decoded = [r for r in steps if r["active"]]
    assert decoded and all(r["decode_steps"] == 8 for r in steps)
    assert all(r["emitted"] >= r["active"] for r in decoded)
    metrics = service.metrics()
    assert metrics["decode_steps"] == 8
    assert metrics["decode_tokens_total"] == sum(r["emitted"] for r in steps)
    assert metrics["h2d_uploads_total"] == service.stats["h2d_uploads"]
    # one sync per 8-token block; stops discard some overrun tokens, so the
    # ratio sits between 1/8 and the all-discarded worst case
    assert 1 / 8 <= metrics["host_syncs_per_token"] <= 1 / 8 + 0.05
