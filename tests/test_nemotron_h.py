"""Nemotron-H through the decode service, against the benchmark's plain
reference (``benchmark/reference/nemotron_h.py``: float32, token-by-token
recurrence, every held expert applied densely), at a tiny size on the CPU with
seeded random weights.

Weights and activations are float32 here, so the program and the reference
differ only in the order of their float32 sums (chunked scan against
recurrence, grouped products against dense ones): logits of size 0.1 agree to
1.5e-7 over these requests, and ``LOGIT_TOL`` is 5e-7, three times that.  A
state pool in bfloat16 moves them by 1.6e-6 (ten times the program's gap, so
the tolerance sits at the geometric middle) and a router in bfloat16 by 1e-4
or, where a pick flips, 6e-3: that is what the last test holds.  ``time_step_min`` / ``time_step_max`` are
50 and 5 times the published ones, so that at 3 to 35 tokens the state has
decayed and refilled as it does over hundreds at the published steps.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import DecodeService, ServingConfig
from accelerate_tpu.models import nemotron_h
from accelerate_tpu.native.kernels import ssm_step as ssm_kernel
from accelerate_tpu.nn import moe
from accelerate_tpu.ops import ssm
from accelerate_tpu.telemetry import flightrec
from benchmark import cells

ref = cells.load_module("reference", "nemotron_h")
family = cells.load_module("families", "nemotron_h")

LOGIT_TOL = 5e-7
CFG = dict(
    hidden_size=32, vocab_size=96, num_hidden_layers=6, hybrid_override_pattern="MEM*EM",
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=8, conv_kernel=4, chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    n_routed_experts=4, router_width=8, expert_offset=2,  # experts 2..5 of 8 are held
    num_experts_per_tok=3, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=24, routed_scaling_factor=2.5, norm_eps=1e-5, max_position_embeddings=128,
    time_step_min=0.05, time_step_max=0.5, time_step_floor=1e-4,
)
SERVICE = dict(max_slots=3, block_size=4, prompt_bucket=16, max_request_len=64)
# (prompt length, tokens to serve): prompts that end inside a chunk (5, 11, 3),
# at a chunk's end inside a bucket (8), at a bucket's end (16) and in a second
# bucket (23); three slots, so later requests start while earlier ones decode
REQUESTS = ((5, 6), (16, 9), (11, 4), (23, 12), (3, 7), (8, 5))


@pytest.fixture(scope="module")
def params():
    return ref.init_params(CFG, 5, jnp.float32)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG["vocab_size"], n).astype(np.int32) for n, _ in REQUESTS]


class Tapped:
    """A service whose family hands every logits vector it computes to the
    host, in order: one ``(1, V)`` a prefill, one ``(slots, V)`` a decode step."""

    def __init__(self, params, tag="plain", **service):
        self.seen = []
        self.model = family.build_model(CFG, params).eval()
        spec = self.model._decoder_spec()
        spec.family = _tapped_family(tag, self.seen)
        self.model._decoder_spec = lambda: spec
        self.service = DecodeService(self.model, ServingConfig(**{**SERVICE, **service}))

    def run(self, prompts, budgets) -> dict:
        """``{request index: (tokens, logits (n_tokens, V))}``."""
        svc = self.service
        rids = [svc.submit(p, max_new_tokens=m) for p, m in zip(prompts, budgets)]
        index = {rid: i for i, rid in enumerate(rids)}
        rows, counted, slot_of = {rid: [] for rid in rids}, dict.fromkeys(rids, 0), {}
        while svc.has_work:
            svc.step()
            jax.effects_barrier()
            got, self.seen[:] = list(self.seen), []
            reqs = {r.rid: r for r in list(svc._slot_req) + list(svc.results.values()) if r is not None}
            slot_of.update({r.rid: s for s, r in enumerate(svc._slot_req) if r is not None})
            # a step's logits: one (1, V) per request it admitted, in admission
            # order, then the decode's (slots, V) over every slot then active
            newly = sorted((reqs[rid] for rid in rids if rid in reqs and not counted[rid]),
                           key=lambda r: r.first_token_t)
            for r, lg in zip(newly, got):
                rows[r.rid].append(lg[0])
                counted[r.rid] = 1
            for rid in rids:
                if rid in reqs and len(reqs[rid].tokens) > counted[rid]:
                    rows[rid].append(got[-1][slot_of[rid]])
                    counted[rid] += 1
        return {index[rid]: (np.asarray(svc.results[rid].tokens), np.stack(rows[rid])) for rid in rids}


_FAMILIES = {}


def _tapped_family(tag, seen):
    """One family object a tag (a jit cache key), its tap pointed at ``seen``."""
    if tag not in _FAMILIES:
        sink = {"to": seen}

        def finalize(g, x, cfg):
            lg = nemotron_h.NEMOTRON_H_DECODER.finalize(g, x, cfg)
            jax.debug.callback(lambda a: sink["to"].append(np.asarray(a)), lg, ordered=True)
            return lg

        _FAMILIES[tag] = (dataclasses.replace(nemotron_h.NEMOTRON_H_DECODER, finalize=finalize), sink)
    fam, sink = _FAMILIES[tag]
    sink["to"] = seen
    return fam


_ref_logits = jax.jit(ref.logits, static_argnames=("st",))


def reference_logits(params, prompt, tokens):
    """The reference's logits at the positions that produced ``tokens`` (one
    compiled length: what lies behind a position does not reach it)."""
    ids = np.zeros(40, np.int32)
    n = len(prompt) + len(tokens) - 1
    ids[:n] = np.concatenate([prompt, tokens[:-1]])
    arrays = {k: v for k, v in params.items() if k != "static"}
    lg = _ref_logits(arrays, jnp.asarray(ids), st=params["static"])
    return np.asarray(lg)[len(prompt) - 1:n]


def worst_gap(params, prompts, served) -> float:
    return max(
        float(np.abs(lg - reference_logits(params, prompts[i], toks)).max())
        for i, (toks, lg) in served.items()
    )


@pytest.fixture(scope="module")
def served(params, prompts):
    tapped = Tapped(params)
    out = tapped.run(prompts, [m for _, m in REQUESTS])
    tapped.service.pool.check_no_leaks()
    assert tapped.service.recompile_events == 0
    return out, tapped.service


# -- (a) prefill + decode through both caches against the full forward ---------
def test_service_logits_match_the_reference(params, prompts, served):
    out, service = served
    assert sorted(len(t) for t, _ in out.values()) == sorted(m for _, m in REQUESTS)
    assert worst_gap(params, prompts, out) < LOGIT_TOL
    # the expert layers' load reached the host beside the tokens
    assert service.stats["expert_tokens"] > 0
    assert service.pool.state_resets == len(REQUESTS)


def test_generate_and_quantized_serving_refuse_in_one_line(params):
    model = family.build_model(CFG, params).eval()
    with pytest.raises(NotImplementedError, match="mixed"):
        model.generate(np.zeros((1, 4), np.int32), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="mixed layer plan"):
        DecodeService(model, ServingConfig(**SERVICE, quantize_weights=8))
    with pytest.raises(NotImplementedError, match="mixed layer plan"):
        DecodeService(model, ServingConfig(**SERVICE, decode_steps=2))


# -- (b) the chunked scan against the recurrence; padding -----------------------
def _recurrence(x, dt, a, b, c, d):
    """Token by token, in float64 numpy."""
    x, dt, a, b, c, d = (np.asarray(v, np.float64) for v in (x, dt, a, b, c, d))
    t, h, p = x.shape
    rep = h // b.shape[1]
    state, ys = np.zeros((h, p, b.shape[2])), []
    for i in range(t):
        bh, ch = np.repeat(b[i], rep, 0), np.repeat(c[i], rep, 0)
        state = np.exp(dt[i] * a)[:, None, None] * state + (dt[i][:, None] * x[i])[:, :, None] * bh[:, None, :]
        ys.append((state * ch[:, None, :]).sum(-1) + d[:, None] * x[i])
    return np.stack(ys), state


@pytest.mark.parametrize("true_len", [3, 8, 11, 16])
def test_chunked_scan_is_the_recurrence_and_padding_stands_still(true_len):
    """``ssd_chunked`` over a 16-token bucket (two chunks of 8) with ``dt`` zeroed
    past ``true_len`` gives the recurrence's outputs on the true positions and
    its state after ``true_len`` tokens; float32 sums in another order: 1e-5."""
    t, h, p, g, n = 16, 4, 8, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(true_len), 5)
    x = jax.random.normal(ks[0], (t, h, p))
    b, c = jax.random.normal(ks[1], (t, g, n)), jax.random.normal(ks[2], (t, g, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (t, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[4], (h,), minval=0.0, maxval=2.5))
    d = jnp.ones((h,))
    masked = jnp.where((jnp.arange(t) < true_len)[:, None], dt, 0.0)
    y, state = ssm.ssd_chunked(x, masked, a, b, c, d, 8)
    want_y, want_state = _recurrence(x[:true_len], dt[:true_len], a, b[:true_len], c[:true_len], d)
    np.testing.assert_allclose(np.asarray(y)[:true_len], want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state), want_state, atol=1e-5, rtol=1e-5)


def test_padded_prefill_leaves_the_unpadded_state_and_tail(params):
    """The same 16 tokens alone in their bucket and padded into a bucket of 24:
    the state and the convolution tail the slot is left with are the same."""
    cfg = family.program_config(CFG)
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, CFG["hidden_size"]))
    _, state, tail = nemotron_h.mamba_prefill(layer, x[:, :16], jnp.int32(16), cfg)
    _, state_p, tail_p = nemotron_h.mamba_prefill(layer, x, jnp.int32(16), cfg)
    np.testing.assert_allclose(np.asarray(state_p), np.asarray(state), atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail_p), np.asarray(tail))


# -- (c) the shares add up; (d) no drop ------------------------------------------
def _uncut_layer(seed=3):
    cfg = dict(CFG, n_routed_experts=8, router_width=8, expert_offset=0, hybrid_override_pattern="E",
               num_hidden_layers=1)
    return cfg, ref.init_params(cfg, seed, jnp.float32)["layers"][0]


@pytest.mark.parametrize("tokens", [1, 40, 600], ids=["one-token", "a-decode-step", "past-a-bucket"])
def test_the_four_shares_add_up_to_the_uncut_layer(tokens):
    """Four chips hold experts 0-1, 2-3, 4-5, 6-7 of one layer.  Their routed
    parts plus the shared expert, counted once, are the uncut reference's layer
    (float32 sums in another order: 1e-5)."""
    cfg, p = _uncut_layer()
    u = jax.random.normal(jax.random.PRNGKey(2), (tokens, cfg["hidden_size"]))
    total = moe.shared_expert_ffn(u, p["shared_up_w"], p["shared_down_w"])
    seen = 0
    for offset in (0, 2, 4, 6):
        part, sizes = moe.held_experts_ffn(
            u, p["router_w"], p["router_bias"], p["up_w"][offset:offset + 2], p["down_w"][offset:offset + 2],
            top_k=cfg["num_experts_per_tok"], scale=cfg["routed_scaling_factor"], expert_offset=offset,
        )
        total = total + part
        seen += int(sizes.sum())
    assert seen == tokens * cfg["num_experts_per_tok"]  # every pick was computed on exactly one chip
    want = ref.experts_layer(p, u, ref.static_of(cfg))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("top_k", [1, 3])
def test_no_token_is_dropped_when_every_token_picks_the_same_experts(top_k):
    """A router that sends all 64 tokens to the same ``top_k`` experts: each of
    them computes all 64 rows (a capacity of 1.25 x the mean would keep 10)."""
    cfg, p = _uncut_layer()
    cfg = dict(cfg, num_experts_per_tok=top_k)
    p = dict(p, router_w=jnp.zeros_like(p["router_w"]),
             router_bias=jnp.where(jnp.arange(8) < top_k, 1.0, 0.0))
    u = jax.random.normal(jax.random.PRNGKey(4), (64, cfg["hidden_size"]))
    part, sizes = moe.held_experts_ffn(
        u, p["router_w"], p["router_bias"], p["up_w"], p["down_w"],
        top_k=top_k, scale=cfg["routed_scaling_factor"],
    )
    assert sizes.tolist() == [64] * top_k + [0] * (8 - top_k)
    want = ref.experts_layer(p, u, ref.static_of(cfg)) - moe.shared_expert_ffn(
        u, p["shared_up_w"], p["shared_down_w"])
    np.testing.assert_allclose(np.asarray(part), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_rows_that_valid_leaves_out_add_nothing_and_are_not_counted():
    """Padding and dead slots ride through the batched product like any row:
    ``valid`` takes their picks out of the sum and out of the load, and what
    they hold (a NaN here) reaches no other row."""
    cfg, p = _uncut_layer()
    u = jax.random.normal(jax.random.PRNGKey(6), (12, cfg["hidden_size"]))
    valid = jnp.arange(12) % 3 != 1
    kw = dict(top_k=cfg["num_experts_per_tok"], scale=cfg["routed_scaling_factor"])
    whole, counted = moe.held_experts_ffn(u, p["router_w"], p["router_bias"], p["up_w"], p["down_w"], **kw)
    dirty = jnp.where(valid[:, None], u, jnp.nan)
    chosen, weights = moe.route_sigmoid_topk(u, p["router_w"], p["router_bias"], **kw)
    part, sizes = moe.held_experts_apply(
        jnp.nan_to_num(dirty), chosen, weights, p["up_w"], p["down_w"], valid=valid)
    assert int(sizes.sum()) == 8 * cfg["num_experts_per_tok"] < int(counted.sum())
    np.testing.assert_array_equal(np.asarray(part)[~np.asarray(valid)], 0.0)
    np.testing.assert_allclose(np.asarray(part)[np.asarray(valid)], np.asarray(whole)[np.asarray(valid)],
                               atol=1e-6, rtol=1e-6)


# -- the served gap: a window's mean, and a far-off token for itself ---------------
@pytest.mark.parametrize("gaps, want", [
    (np.full(200, 0.1), 0.1),  # tokens off often, by little: the mean
    (np.r_[np.zeros(100), 3.0, np.zeros(99)], 3.0 / 64),  # one near-tie that flipped: a sixty-fourth of it
    (np.r_[np.zeros(100), 8.0, np.zeros(99)], 8.0),  # one token that is simply wrong stands for itself
    (np.r_[0.5, 6.0, 0.1], 6.0),  # in a request shorter than the window too
], ids=["often-by-little", "one-flip", "one-wrong-token", "short-request"])
def test_the_served_gap_is_a_windows_mean_but_a_far_off_token_stands_for_itself(gaps, want):
    got = ref.windowed(gaps)
    assert got.shape == gaps.shape and got.dtype == np.float32
    assert float(got.max()) == pytest.approx(want, rel=1e-5)
    assert ref.FAR_OFF > 3.1 and ref.GAP_WINDOW == 64  # what the limit's readings were taken with


# -- (e) states never mix across slots; a slot's state is reset -------------------
def test_a_nan_in_a_dead_slot_reaches_no_live_one_and_admission_resets_it(params, prompts, served):
    """Slot 2's state and tail are NaN before anything is admitted.  Requests 0
    and 1 run in slots 0 and 1 beside it: their logits are those of the clean
    run, bit for bit.  Requests 2 and 3 then go through slot 2 and the retired
    slots: the prefill that admits them writes the state whole."""
    clean, _ = served
    tapped = Tapped(params)
    svc = tapped.service
    svc._state = {
        "ssm": svc._state["ssm"].at[:, 2].set(jnp.nan),
        "conv": svc._state["conv"].at[:, 2].set(jnp.nan),
    }
    first = tapped.run(prompts[:2], [m for _, m in REQUESTS[:2]])
    assert np.isnan(np.asarray(svc._state["ssm"][:, 2])).all()  # nobody touched it
    for i in (0, 1):
        np.testing.assert_array_equal(first[i][1], clean[i][1])
    later = tapped.run(prompts[2:5], [m for _, m in REQUESTS[2:5]])  # three at once: all slots, 2 among them
    assert worst_gap(params, prompts[2:5], later) < LOGIT_TOL
    assert not np.isnan(np.asarray(svc._state["ssm"])).any()
    svc.pool.check_no_leaks()


# -- (e') the decode step's kernel: the live slots alone, in place ----------------
@pytest.mark.parametrize("live", [
    (0, 0, 0, 0, 0, 0), (1, 1, 1, 1, 1, 1), (0, 0, 1, 0, 0, 0), (1, 0, 1, 1, 0, 1), (0, 0, 0, 0, 0, 1),
], ids=["none-live", "all-live", "one-live", "non-contiguous", "last-slot-only"])
def test_the_step_kernel_is_the_plain_step_on_live_slots_and_leaves_the_rest(live):
    """``native/kernels/ssm_step.py`` (the interpreter here) against
    ``ops/ssm.py::ssm_step`` on layer 1 of a three-layer pool: the live slots'
    ``y`` and new state agree to float32 rounding (1e-5, as the scan's test
    holds: one sum over ``N`` in another order); the dead slots' rows and the other layers' rows are the
    pool's own, bit for bit, though the dead rows hold NaN; a dead slot's ``y``
    is zeros."""
    n_layers, slots, h, p, n, g = 3, 6, 8, 8, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(sum(live) + 7), 7)
    live = np.asarray(live, bool)
    pool = jax.random.normal(ks[0], (n_layers, slots, h, p, n))
    pool = pool.at[1].set(jnp.where(live[:, None, None, None], pool[1], jnp.nan))
    x = jax.random.normal(ks[1], (slots, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, h)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[3], (h,), minval=0.0, maxval=2.5))
    b, c = jax.random.normal(ks[4], (slots, g, n)), jax.random.normal(ks[5], (slots, g, n))
    d = jax.random.normal(ks[6], (h,))
    want_y, want_state = (np.asarray(t) for t in ssm.ssm_step(pool[1], x, dt, a, b, c, d))
    y, new = (np.asarray(t) for t in ssm_kernel.ssm_step_live(pool, 1, jnp.asarray(live), x, dt, a, b, c, d))
    before = np.asarray(pool)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[1][live], want_state[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(y[~live], 0.0)
    assert np.isfinite(y).all()
    assert new[1][~live].tobytes() == before[1][~live].tobytes()
    assert new[[0, 2]].tobytes() == before[[0, 2]].tobytes()


def test_state_slots_walked_counts_the_decoding_slots_a_mamba_layer(params, prompts):
    """``stats["state_slots_walked"]``: a request of ``m`` tokens decodes ``m -
    1`` of them (the prefill samples the first), each in every Mamba layer (3
    of ``MEM*EM``) — host arithmetic, and the ring's step spans carry it."""
    from accelerate_tpu.telemetry import flightrec

    tapped = Tapped(params)
    tapped.run(prompts[:4], [m for _, m in REQUESTS[:4]])
    service = tapped.service
    want = CFG["hybrid_override_pattern"].count("M") * sum(m - 1 for _, m in REQUESTS[:4])
    assert service.stats["state_slots_walked"] == want
    steps = [e for e in flightrec.recorder().snapshot() if e["kind"] == "atpu/serve/step"]
    mine = steps[-service.stats["steps"]:]
    assert sum(e.get("state_slots_walked", 0) for e in mine) == want


# -- (f) a lower precision than stated fails (a) -----------------------------------
def _bf16(x):
    return x.astype(jnp.bfloat16).astype(x.dtype)


@pytest.mark.parametrize("what", ["state", "router"])
def test_a_lower_precision_than_stated_fails_the_comparison(params, prompts, monkeypatch, what):
    """The state pool, or the router with its weights, in bfloat16: the logits
    leave the reference by more than 2.5 times ``LOGIT_TOL`` (state: 3.1
    times; router: 200 times and more).  ``D`` is 0 here, so that the recurrence
    carries the Mamba layers' whole output — as they are, the program passes."""
    params = dict(params, layers=[
        dict(layer, d=jnp.zeros_like(layer["d"])) if "d" in layer else layer for layer in params["layers"]
    ])
    budgets = [m for _, m in REQUESTS]
    assert worst_gap(params, prompts, Tapped(params).run(prompts, budgets)) < LOGIT_TOL
    if what == "state":
        # what the programs run: the decode step's kernel over the whole pool
        # (rounding a row twice is rounding it once), the prefill's scan
        step, chunked = ssm_kernel.ssm_step_live, ssm.ssd_chunked
        monkeypatch.setattr(ssm_kernel, "ssm_step_live",
                            lambda pool, *a, **kw: (lambda y, p: (y, _bf16(p)))(*step(_bf16(pool), *a, **kw)))
        monkeypatch.setattr(ssm, "ssd_chunked", lambda *a: (lambda y, s: (y, _bf16(s)))(*chunked(*a)))
    else:
        route = moe.route_sigmoid_topk

        def low(x, w, b, **kw):
            chosen, weights = route(_bf16(x), _bf16(w), b, **kw)
            return chosen, _bf16(weights)

        monkeypatch.setattr(nemotron_h, "route_sigmoid_topk", low)
    out = Tapped(params, tag=what).run(prompts, budgets)
    assert worst_gap(params, prompts, out) > 2.5 * LOGIT_TOL


# -- the benchmark's own runner over the family, at a tiny size ------------------
@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """A throw-away copy of the benchmark with this file's configuration as a
    cell of its own, as ``benchmark/tests/helpers.py`` makes one for GPT-2."""
    import json
    import os
    import shutil

    from benchmark import harness

    root = str(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(cells.HERE, bench, ignore=shutil.ignore_patterns("out", "tests", "__pycache__"))
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config = dict(CFG, family="nemotron_h", n_head=CFG["num_attention_heads"],
                  precision={"mixed_precision": "bf16", "control": {"serve": "int8"}})
    mix = {"kind": "serve", "rate_per_s": 30.0, "prompt_len": {"dist": "loguniform", "low": 4, "high": 30},
           "output_len": {"dist": "loguniform", "low": 3, "high": 9}, "sampling": "greedy",
           "service": {"max_slots": 4, "block_size": 4, "prompt_bucket": 16, "max_request_len": 64},
           "check_requests": 5, "trace_seconds": 0}
    for path, what in (("configs/tiny-h.json", config), ("traffic/chat-tiny.json", mix),
                       ("limits/tiny-h.chat.json", {"limits": {"served_logit_gap": 0.05, "unfinished_requests": 0}})):
        with open(os.path.join(bench, path), "w") as f:
            json.dump(what, f)
    manifest["configs"].append({"name": "tiny-h", "source": "test", "reduced": [], "why": "test",
                                "file": "benchmark/configs/tiny-h.json"})
    manifest["workloads"].append({"name": "tiny-h.chat", "config": "tiny-h", "traffic": "chat-tiny",
                                  "chips": 1, "why": "test"})
    real = "nemotron-3-nano-30b-a3b.serve-chat"
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if real in metric.get("workloads", ()):
            metric["workloads"].append("tiny-h.chat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return cells.resolve("tiny-h.chat", root), harness


def test_the_benchmarks_runner_serves_the_family_and_its_readers_find_the_load(tiny_cell, monkeypatch):
    """``runners/serve.py`` as it is, under ``prepare(mixed_precision="bf16")``:
    every request finishes, nothing recompiles, the served tokens lie within
    0.05 logit of the float32 reference's best (bfloat16 products at this size
    read under 0.02), and the per-layer readers that need no device trace read
    the ``moe_load`` records off the ring; those that need one return nothing."""
    import time

    cell, harness = tiny_cell
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 0)
    monkeypatch.setattr(harness, "memory_in_use_bytes", lambda: 0)
    # the ring as the benchmark's own process starts with it (this process's
    # compiles would fill the shared one)
    rec = flightrec.FlightRecorder()
    monkeypatch.setattr(flightrec, "_RECORDER", rec)
    out = cell.runner.run(cell, 2**31 + 77, 0.6, False, time.perf_counter(),
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    correct, compared = harness.decide(out["numbers"], cell.limits)
    assert correct, compared
    assert out["failed"] == 0 and out["counters"]["recompile_events"] == 0
    assert out["notes"]["tokens_compared"] > 15
    # off the chip the readers' part ends at the ring's last program span: read
    # the ring as it stood at the run's last engine step, since the reference's
    # compiles and the collection that frees the program come after it
    last_step = max(e["end_ns"] for e in rec.spans(0, rec.now_ns())[0] if e["name"] == "atpu/serve/step")
    monkeypatch.setattr(rec, "now_ns", lambda: last_step)
    ctx = {"cell": cell, "counters": out["counters"], "planes": None, "summary": None,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = {name: cell.layer_metric(name).read(ctx) for name in cell.per_layer}
    assert 0 < read["serve_step_mfu.nemotron-h"] < 100
    assert 0 < read["decode_experts_touched"] <= CFG["n_routed_experts"]
    assert read["moe_load_max_over_mean"] >= 1.0
    assert read["host_syncs_per_token"] == 1.0
    for needs_a_trace in ("decode_ssm_ms", "decode_moe_ms", "ssm_step_roofline", "moe_experts_roofline",
                          "ssd_prefill_roofline", "prefill_ssm_pct", "decode_hbm_pct.nemotron-h"):
        assert read[needs_a_trace] is None


def test_prefill_scopes_are_read_over_every_bucket_and_nothing_is_registered():
    """A trace holds executions of two prefill buckets under one module name;
    each program's text has names the other lacks.  ``hybrid_readers.scope_ms``
    reads them over both texts, and leaves the program's registry as it was:
    what other readers of the same context see does not change."""
    from accelerate_tpu.telemetry import profiler
    from benchmark import hybrid_readers, span_readers

    def text(names):
        return "\n".join(
            f'  %{n} = f32[] add(), metadata={{op_name="jit(_prefill_jit)/{scope}/add"}}' if scope
            else f"  %{n} = f32[] custom-call()" for n, scope in names
        ) + "\n"

    small = [("fusion.1", "atpu_serve_ssm_scan"), ("fusion.2", "atpu_serve_moe_route")]
    large = [("fusion.1", "atpu_serve_ssm_scan"), ("fusion.9", "atpu_serve_ssm_in"), ("copy-done.3", None)]
    profiler.register_program("jit__prefill_jit", lambda: text(small), key="test-small")
    profiler.register_program("jit__prefill_jit", lambda: text(large), key="test-large")
    before = [(p.name, id(p)) for p in profiler.registered_programs()]
    ms = 1_000_000
    ops = [["fusion.1", 0, 2 * ms], ["fusion.2", 2 * ms, 1 * ms],  # the small bucket's execution
           ["fusion.1", 10 * ms, 4 * ms], ["fusion.9", 14 * ms, 1 * ms], ["copy-done.3", 15 * ms, 3 * ms]]
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit__prefill_jit(1)", 0, 3 * ms], ["jit__prefill_jit(2)", 10 * ms, 8 * ms]]},
    ]}]
    ctx = {"planes": planes}
    assert hybrid_readers.scope_ms(ctx, hybrid_readers.PREFILL, "atpu_serve_ssm_") == pytest.approx((3.5, 5.5))
    assert hybrid_readers.scope_ms(ctx, hybrid_readers.PREFILL, "atpu_serve_moe_") == pytest.approx((0.5, 5.5))
    assert hybrid_readers.scope_ms(ctx, hybrid_readers.PREFILL, "unscoped") == pytest.approx((1.5, 5.5))
    assert [(p.name, id(p)) for p in profiler.registered_programs()] == before
    # the registry's own lookup still answers with the newest bucket alone: under its coverage
    assert span_readers.device_ms_by_scope(ctx, hybrid_readers.PREFILL) is None
