"""Olmo-Hybrid through the decode service, against the benchmark's plain
reference (``benchmark/reference/olmo_hybrid.py``: float32, the delta rule token
by token), at a tiny size on the CPU with seeded random weights: both kinds of
layer, two periods, so the engine scans the plan by its period and its
attention layers take the paged-attention kernel (interpreted here).

Weights and activations are float32 here, so the program and the reference
differ only in the order of their float32 sums (the chunked WY form against the
recurrence, the packed state's folded sums, the kernel's chunked softmax).
Logits of size 0.11 agree to 1.9e-7 over these requests; the state pool in
bfloat16 moves them by 3.2e-5, the decay by 5.2e-5 and the l2 norms by 4.0e-4,
so ``LOGIT_TOL`` is 2.5e-6, the geometric middle of the program's gap and the
nearest of those, and the last test but one holds that each fails it five times
over.  ``dt_bias`` is shifted up by 4, so that at 3 to 35 tokens the state
has decayed and been overwritten as it is over hundreds at the published steps.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import DecodeService, ServingConfig
from accelerate_tpu.models import olmo_hybrid
from accelerate_tpu.native.kernels import gdn_step as gdn_kernel
from accelerate_tpu.ops import delta_rule, ssm
from accelerate_tpu.telemetry import flightrec
from benchmark import cells
from benchmark import flops_olmo_hybrid as costs

ref = cells.load_module("reference", "olmo_hybrid")
family = cells.load_module("families", "olmo_hybrid")

LOGIT_TOL = 2.5e-6
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
CFG = dict(
    hidden_size=32, vocab_size=96, intermediate_size=48, num_hidden_layers=8, layer_types=PERIOD * 2,
    num_attention_heads=4, num_key_value_heads=4, linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rms_norm_eps=1e-6, max_position_embeddings=128,
    assumed_sizes={"chunk_size": 8},
)
SERVICE = dict(max_slots=3, block_size=4, prompt_bucket=16, max_request_len=64)
# (prompt length, tokens to serve): prompts that end inside a chunk (5, 11, 3),
# at a chunk's end inside a bucket (8), at a bucket's end (16) and in a second
# bucket (23); three slots, so later requests start while earlier ones decode
# and every slot is used again
REQUESTS = ((5, 6), (16, 9), (11, 4), (23, 12), (3, 7), (8, 5))


def _params(seed=5, **over):
    params = ref.init_params(dict(CFG, **over), seed, jnp.float32)
    # faster clocks than published (module docstring); layers 0-2 of a period are linear
    params["layers"] = [
        dict(layer, dt_bias=layer["dt_bias"] + 4.0) if "dt_bias" in layer else layer
        for layer in params["layers"]
    ]
    return params


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG["vocab_size"], n).astype(np.int32) for n, _ in REQUESTS]


class Tapped:
    """A service whose family hands every logits vector it computes to the
    host, in order: one ``(1, V)`` a prefill, one ``(slots, V)`` a decode step."""

    def __init__(self, params, tag="plain", cfg=CFG, **service):
        self.seen = []
        self.model = family.build_model(cfg, params).eval()
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
            lg = olmo_hybrid.OLMO_HYBRID_DECODER.finalize(g, x, cfg)
            jax.debug.callback(lambda a: sink["to"].append(np.asarray(a)), lg, ordered=True)
            return lg

        _FAMILIES[tag] = (dataclasses.replace(olmo_hybrid.OLMO_HYBRID_DECODER, finalize=finalize), sink)
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
    assert service.pool.state_resets == len(REQUESTS)
    # no expert layer: the programs hand the host no load, and none is recorded
    assert service.stats["expert_tokens"] == 0


def test_the_plan_is_scanned_by_its_period_and_held_once(params):
    """Eight layers are two repeats of four: the model holds four stacks of two
    (the reference's own arrays), the engine sees a period of 4, and the state
    pool is as deep as the plan has linear layers, in the packed layout."""
    from accelerate_tpu.models.generation import layer_plan, plan_period

    tapped = Tapped(params)
    spec = tapped.model._decoder_spec()
    g, layers = spec.stack()
    kinds = layer_plan(spec.family, spec.cfg)
    assert len(kinds) == 8 and len(layers) == 4 and plan_period(kinds, len(layers)) == 4
    assert all(leaf.shape[0] == 2 for layer in layers for leaf in layer.values())
    assert layers[0]["q_w"] is params["layers"][0]["q_w"] and g["head"] is params["head"]
    state = tapped.service._state
    # 8 x 16 float32 a head packs 8 rows of 16 onto 128 lanes
    assert state["ssm"].shape == (6, 3, 4, 1, 128) and state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (6, 3, 3, 2 * 32 + 64)
    assert tapped.service._k_pool.shape[0] == 2


def test_a_plan_that_is_no_repeat_is_unrolled_and_serves_the_same_mathematics(prompts):
    """Five layers (a period and one more) are no repeat: a dict a layer, the
    unrolled walk, the gather path for the one attention layer — the same
    family functions, and the reference's logits."""
    cfg = dict(CFG, num_hidden_layers=5, layer_types=PERIOD + ["linear_attention"])
    params = _params(**{k: cfg[k] for k in ("num_hidden_layers", "layer_types")})
    tapped = Tapped(params, tag="unrolled", cfg=cfg)
    assert len(tapped.model._decoder_spec().stack()[1]) == 5
    out = tapped.run(prompts[:3], [m for _, m in REQUESTS[:3]])
    assert worst_gap(params, prompts, out) < LOGIT_TOL


def test_generate_quantized_serving_and_decode_steps_refuse_in_one_line(params):
    model = family.build_model(CFG, params).eval()
    with pytest.raises(NotImplementedError, match="mixed"):
        model.generate(np.zeros((1, 4), np.int32), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="mixed layer plan"):
        DecodeService(model, ServingConfig(**SERVICE, quantize_weights=8))
    with pytest.raises(NotImplementedError, match="mixed layer plan"):
        DecodeService(model, ServingConfig(**SERVICE, decode_steps=2))


# -- (b) the chunked scan against the recurrence; padding -----------------------
def _recurrence(q, k, v, g, beta):
    """Token by token, in float64 numpy."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    norm = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = norm(q) * q.shape[-1] ** -0.5, norm(k)
    state, out = np.zeros((k.shape[1], k.shape[2], v.shape[2])), []
    for t in range(len(q)):
        state = np.exp(g[t])[:, None, None] * state
        seen = (state * k[t][:, :, None]).sum(1)
        state = state + k[t][:, :, None] * (beta[t][:, None] * (v[t] - seen))[:, None, :]
        out.append((state * q[t][:, :, None]).sum(1))
    return np.stack(out), state


def _draws(seed, t=16, h=4, dk=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = jax.random.normal(ks[0], (t, h, dk)), jax.random.normal(ks[1], (t, h, dk))
    v = jax.random.normal(ks[2], (t, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (t, h)))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (t, h)))  # up to 2: negative eigenvalues
    return q, k, v, g, beta


@pytest.mark.parametrize("true_len", [3, 8, 11, 16])
def test_chunked_scan_is_the_recurrence_and_padding_stands_still(true_len):
    """``delta_rule_chunked`` over a 16-token bucket (two chunks of 8) with
    ``beta`` and ``g`` zeroed past ``true_len`` gives the recurrence's outputs on
    the true positions and its state after ``true_len`` tokens; float32 sums in
    another order and a triangular solve: 2e-5."""
    q, k, v, g, beta = _draws(true_len)
    true = (jnp.arange(16) < true_len)[:, None]
    o, state = delta_rule.delta_rule_chunked(q, k, v, jnp.where(true, g, 0.0), jnp.where(true, beta, 0.0), 8)
    want_o, want_state = _recurrence(*(x[:true_len] for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(o)[:true_len], want_o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(state), want_state, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n, keys", [(8, "random"), (64, "random"), (64, "repeated"), (48, "mixed")])
def test_the_chunks_triangular_system_is_inverted_without_powers(n, keys):
    """``(I + tril(beta K K^T, -1))^-1`` by block forward substitution against
    float64 numpy, for random keys, for ONE key repeated at ``beta = 2`` (the
    same token again and again: the matrix is full of 2s, its 32nd power holds
    1e27, the inverse only 1s and 2s) and for a mix, at a size that is no power
    of two as well: 1e-6."""
    rng = np.random.default_rng(n)
    one = np.tile(rng.normal(size=(1, 96)), (n, 1))
    k = {"random": rng.normal(size=(n, 96)), "repeated": one,
         "mixed": np.where(rng.random((n, 1)) < 0.5, one, rng.normal(size=(n, 96)))}[keys]
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = np.full(n, 2.0) if keys == "repeated" else 2 * rng.random(n)
    a = np.tril(beta[:, None] * (k @ k.T), -1)
    got = delta_rule._unit_lower_inverse(jnp.asarray(a, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.linalg.inv(np.eye(n) + a), atol=1e-6, rtol=1e-6)


def test_the_one_token_step_on_the_packed_state_is_the_recurrence():
    """Three slots walk 16 tokens of their own through ``delta_rule_step`` on
    the packed layout (8 rows of 16 on 128 lanes): outputs and the unpacked
    state are the recurrence's, slot by slot."""
    draws = [_draws(20 + s) for s in range(3)]
    state = jnp.zeros((3, 4, 1, 128), jnp.float32)
    outs = []
    for t in range(16):
        o, state = delta_rule.delta_rule_step(state, *(jnp.stack([d[j][t] for d in draws]) for j in range(5)))
        outs.append(o)
    assert state.shape == (3, 4, 1, 128)
    for s, d in enumerate(draws):
        want_o, want_state = _recurrence(*d)
        np.testing.assert_allclose(np.stack([np.asarray(o[s]) for o in outs]), want_o, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(delta_rule.unpack_state(state[s], 16)), want_state, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("d_k, d_v, rows, lanes", [(96, 192, 48, 384), (8, 16, 1, 128), (128, 128, 128, 128), (6, 16, 6, 16)])
def test_a_packed_state_lies_on_whole_tiles_of_lanes(d_k, d_v, rows, lanes):
    s = jnp.arange(2 * d_k * d_v, dtype=jnp.float32).reshape(2, d_k, d_v)
    packed = delta_rule.pack_state(s)
    assert packed.shape == (2, rows, lanes)
    np.testing.assert_array_equal(np.asarray(delta_rule.unpack_state(packed, d_v)), np.asarray(s))


def test_padded_prefill_leaves_the_unpadded_state_and_tail(params):
    """The same 16 tokens alone in their bucket and padded into a bucket of 24:
    the state and the convolution tail the slot is left with are the same."""
    cfg = family.program_config(CFG)
    layer = {k: v[0] for k, v in params["layers"][0].items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, CFG["hidden_size"]))
    _, state, tail = olmo_hybrid.gdn_prefill(layer, x[:, :16], jnp.int32(16), cfg)
    _, state_p, tail_p = olmo_hybrid.gdn_prefill(layer, x, jnp.int32(16), cfg)
    np.testing.assert_allclose(np.asarray(state_p), np.asarray(state), atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail_p), np.asarray(tail))


# -- (c) beta = 2 sigmoid: a negative eigenvalue ----------------------------------
def test_beta_over_one_flips_the_sign_of_what_the_state_holds_along_k():
    """With ``v = 0`` and no decay a step leaves ``S^T k`` at ``(1 - beta)``
    times what it was: the transition's eigenvalue along ``k``, negative once
    ``beta > 1``."""
    q, k, v, _, _ = _draws(9, t=1)
    state0 = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 8, 16))
    unit = np.asarray(delta_rule.l2norm(k))  # (1, 4, 8)
    before = (np.asarray(state0) * unit[..., None]).sum(2)
    for beta in (0.5, 1.5):
        _, state = delta_rule.delta_rule_step(
            delta_rule.pack_state(state0), q, k, jnp.zeros_like(v), jnp.zeros((1, 4)), jnp.full((1, 4), beta))
        after = (np.asarray(delta_rule.unpack_state(state, 16)) * unit[..., None]).sum(2)
        np.testing.assert_allclose(after, (1.0 - beta) * before, atol=1e-5, rtol=1e-4)


def test_dropping_the_factor_two_changes_the_served_tokens(prompts):
    """``b`` projections fifty times the drawn ones spread ``beta`` over (0, 2).
    The program serves what the reference gives with ``linear_allow_neg_eigval``;
    the reference without it (``beta = sigmoid(b)``, under 1) puts another token
    first at some served position, and its logits are a thousand tolerances
    away: a program that dropped the factor would fail test (a)."""
    params = _params(seed=7)
    params["layers"] = [dict(l, b_w=50.0 * l["b_w"]) if "b_w" in l else l for l in params["layers"]]
    budgets = [12] * 3
    out = Tapped(params, tag="neg").run(prompts[:3], budgets)
    assert worst_gap(params, prompts, out) < LOGIT_TOL
    halved = dict(params, static=params["static"]._replace(linear_allow_neg_eigval=False))
    assert worst_gap(halved, prompts, out) > 1000 * LOGIT_TOL
    assert any(
        (np.argmax(reference_logits(halved, prompts[i], toks), axis=-1) != toks).any()
        for i, (toks, _) in out.items()
    )


# -- (d) states never mix across slots; a slot's state is reset -------------------
def test_a_nan_in_a_dead_slot_reaches_no_live_one_and_admission_resets_it(params, prompts, served):
    """Slot 2's state and tail are NaN before anything is admitted.  Requests 0
    and 1 run in slots 0 and 1 beside it: their logits are those of the clean
    run, bit for bit.  Requests 2 to 4 then go through slot 2 and the retired
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


# -- (d') the decode step's kernel: the live slots alone, in place ----------------
@pytest.mark.parametrize("live", [
    (0, 0, 0, 0, 0, 0), (1, 1, 1, 1, 1, 1), (0, 0, 1, 0, 0, 0), (1, 0, 1, 1, 0, 1), (0, 0, 0, 0, 0, 1),
], ids=["none-live", "all-live", "one-live", "non-contiguous", "last-slot-only"])
def test_the_step_kernel_is_the_plain_step_on_live_slots_and_leaves_the_rest(live):
    """``native/kernels/gdn_step.py`` (the interpreter here) against
    ``ops/delta_rule.py::delta_rule_step`` on layer 1 of a three-layer pool,
    packed two k rows a row of lanes as the cell's state is (``d_k`` 16,
    ``d_v`` 64: ``(8, 128)``): the live slots' ``o`` and new state agree to
    float32 rounding (1e-5: the sums over k rows in another order); the dead
    slots' rows and the other layers' rows are the pool's own, bit for bit,
    though the dead rows hold NaN; a dead slot's ``o`` is zeros."""
    n_layers, slots, h, d_k, d_v = 3, 6, 3, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(sum(live) + 11), 6)
    live = np.asarray(live, bool)
    pool = delta_rule.pack_state(jax.random.normal(ks[0], (n_layers, slots, h, d_k, d_v)))
    assert pool.shape[-2:] == (8, 128)
    pool = pool.at[1].set(jnp.where(live[:, None, None, None], pool[1], jnp.nan))
    q, k = jax.random.normal(ks[1], (slots, h, d_k)), jax.random.normal(ks[2], (slots, h, d_k))
    v = jax.random.normal(ks[3], (slots, h, d_v))
    g = -jax.nn.softplus(jax.random.normal(ks[4], (slots, h)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (slots, h)))  # past 1: a negative eigenvalue
    want_o, want_state = (np.asarray(t) for t in delta_rule.delta_rule_step(pool[1], q, k, v, g, beta))
    o, new = (np.asarray(t) for t in gdn_kernel.gdn_step_live(pool, 1, jnp.asarray(live), q, k, v, g, beta))
    before = np.asarray(pool)
    np.testing.assert_allclose(o[live], want_o[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[1][live], want_state[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(o[~live], 0.0)
    assert np.isfinite(o).all()
    assert new[1][~live].tobytes() == before[1][~live].tobytes()
    assert new[[0, 2]].tobytes() == before[[0, 2]].tobytes()


def test_state_slots_walked_counts_the_decoding_slots_a_linear_layer(params, prompts):
    """``stats["state_slots_walked"]``: a request of ``m`` tokens decodes ``m -
    1`` of them (the prefill samples the first), each in every linear layer (6
    of ``[linear x 3, full] x 2``; the cell's 16 layers hold 12) — what the
    kernel walks, host arithmetic, and the ring's step spans carry it."""
    tapped = Tapped(params)
    tapped.run(prompts[:4], [m for _, m in REQUESTS[:4]])
    service = tapped.service
    want = PERIOD.count("linear_attention") * 2 * sum(m - 1 for _, m in REQUESTS[:4])
    assert service.stats["state_slots_walked"] == want
    steps = [e for e in flightrec.recorder().snapshot() if e["kind"] == "atpu/serve/step"]
    mine = steps[-service.stats["steps"]:]
    assert sum(e.get("state_slots_walked", 0) for e in mine) == want


# -- (e) a lower precision than stated fails (a) -----------------------------------
def _bf16(x):
    return x.astype(jnp.bfloat16).astype(x.dtype)


@pytest.mark.parametrize("what", ["state", "decay", "l2norm"])
def test_a_lower_precision_than_stated_fails_the_comparison(params, prompts, monkeypatch, what):
    """The state pool, the decay or the l2 norms in bfloat16: the logits leave
    the reference by more than five times ``LOGIT_TOL`` (13, 20 and 160 times)."""
    if what == "state":
        # what the programs run: the decode step's kernel over the whole pool
        # (rounding a row twice is rounding it once), the prefill's scan
        step, chunked = gdn_kernel.gdn_step_live, delta_rule.delta_rule_chunked
        monkeypatch.setattr(gdn_kernel, "gdn_step_live",
                            lambda pool, *a, **kw: (lambda o, p: (o, _bf16(p)))(*step(_bf16(pool), *a, **kw)))
        monkeypatch.setattr(delta_rule, "delta_rule_chunked", lambda *a: (lambda o, s: (o, _bf16(s)))(*chunked(*a)))
    elif what == "decay":
        exact = olmo_hybrid._decay_and_beta
        monkeypatch.setattr(olmo_hybrid, "_decay_and_beta", lambda *a: (lambda g, b: (_bf16(g), b))(*exact(*a)))
    else:
        exact = delta_rule.l2norm
        monkeypatch.setattr(delta_rule, "l2norm", lambda x, eps=1e-6: _bf16(exact(x, eps)))
    out = Tapped(params, tag=what).run(prompts, [m for _, m in REQUESTS])
    assert worst_gap(params, prompts, out) > 5 * LOGIT_TOL


# -- the yardstick's counts against the configuration and the program -------------
def test_the_costs_count_the_configurations_parameters_and_the_programs_leaves(params):
    import json
    import os

    with open(os.path.join(cells.HERE, "configs", "olmo-hybrid-7b.json")) as f:
        real = json.load(f)
    assert real["parameters_stored"] == costs.param_count(real) == ref.param_count(real)
    assert abs(costs.param_count(real) / 4.100e9 - 1) < 0.005
    assert real["reduced"] == ["num_hidden_layers", "layer_types"]
    assert real["layer_types"] == PERIOD * 4 and real["num_hidden_layers"] == 16
    # 2 B a parameter in matrices + head read a step; the state and the KV a slot and token
    assert costs.state_bytes_per_slot(real) == 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert costs.kv_bytes_per_token(real) == 4 * 2 * 3840 * 2
    model = family.build_model(CFG, params)
    leaves = sum(int(np.prod(p.data.shape)) for _, p in model.named_parameters())
    assert leaves == costs.param_count(CFG) == ref.param_count(CFG)


def test_importing_the_package_imports_neither_the_family_nor_its_ops():
    import subprocess
    import sys

    code = ("import sys, accelerate_tpu, accelerate_tpu.models; "
            "bad = [m for m in ('accelerate_tpu.models.olmo_hybrid', 'accelerate_tpu.ops.delta_rule') "
            "if m in sys.modules]; print(bad); sys.exit(bool(bad))")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stdout + done.stderr


# -- (f) the benchmark's own runner over the family, at a tiny size ---------------
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
    config = dict(CFG, family="olmo_hybrid", n_head=CFG["num_attention_heads"],
                  precision={"mixed_precision": "bf16", "control": {"serve": "int8"}})
    mix = {"kind": "serve", "rate_per_s": 30.0, "prompt_len": {"dist": "loguniform", "low": 4, "high": 30},
           "output_len": {"dist": "loguniform", "low": 3, "high": 9}, "sampling": "greedy",
           "service": {"max_slots": 4, "block_size": 4, "prompt_bucket": 16, "max_request_len": 64},
           "check_requests": 5, "trace_seconds": 0}
    for path, what in (("configs/tiny-o.json", config), ("traffic/longout-tiny.json", mix),
                       ("limits/tiny-o.longout.json", {"limits": {"served_logit_gap": 0.05, "unfinished_requests": 0}})):
        with open(os.path.join(bench, path), "w") as f:
            json.dump(what, f)
    manifest["configs"].append({"name": "tiny-o", "source": "test", "reduced": [], "why": "test",
                                "file": "benchmark/configs/tiny-o.json"})
    manifest["workloads"].append({"name": "tiny-o.longout", "config": "tiny-o", "traffic": "longout-tiny",
                                  "chips": 1, "why": "test"})
    real = "olmo-hybrid-7b.serve-longout"
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if real in metric.get("workloads", ()):
            metric["workloads"].append("tiny-o.longout")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return cells.resolve("tiny-o.longout", root), harness


NEW_METRICS = ("serve_step_mfu.olmo-hybrid", "decode_hbm_pct.olmo-hybrid", "decode_gdn_ms", "decode_mlp_ms",
               "prefill_gdn_pct", "gdn_step_roofline", "gdn_prefill_roofline")


def test_the_benchmarks_runner_serves_the_family_and_its_readers_find_their_scopes(tiny_cell, monkeypatch):
    """``runners/serve.py`` as it is, under ``prepare(mixed_precision="bf16")``:
    every request finishes, nothing recompiles, the served tokens lie within
    0.05 logit of the float32 reference's best.  The reader that needs no
    device trace reads the launches off the ring; those that need one return
    nothing here, and every scope they would read is in the text of the program
    they read it from, with no scope of another family's readers."""
    import time

    from accelerate_tpu.telemetry import profiler
    from benchmark import hybrid_readers

    cell, harness = tiny_cell
    assert set(NEW_METRICS) <= set(cell.per_layer) and "decode_ssm_ms" not in cell.per_layer
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 0)
    monkeypatch.setattr(harness, "memory_in_use_bytes", lambda: 0)
    # the program registry as the benchmark's own process starts with it: what
    # earlier tests of this process registered (an unrolled plan's decode among
    # them) is not this cell's
    monkeypatch.setattr(profiler, "_programs", {})
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
    assert 0 < read["serve_step_mfu.olmo-hybrid"] < 100
    assert read["host_syncs_per_token"] == 1.0
    scopes = {
        module: {s for p in profiler.registered_programs() if module in p.name for s in p.scope_map().values()}
        for module in (hybrid_readers.DECODE, hybrid_readers.PREFILL)
    }
    for name in NEW_METRICS[2:]:
        assert read[name] is None
        reader = cell.layer_metric(name)
        module = hybrid_readers.PREFILL if name.startswith(("prefill", "gdn_prefill")) else hybrid_readers.DECODE
        for prefix in reader.SCOPES:
            assert any(s.startswith(prefix) for s in scopes[module]), (name, prefix, sorted(scopes[module]))
    assert read["decode_hbm_pct.olmo-hybrid"] is None
    for module, found in scopes.items():
        assert {"atpu_serve_qkv", "atpu_serve_kv_write", "atpu_serve_attend", "atpu_serve_out_mlp",
                "atpu_serve_gdn_in", "atpu_serve_gdn_conv", "atpu_serve_gdn_out", "atpu_serve_mlp"} <= found, module
        assert not [s for s in found if s.startswith(("atpu_serve_ssm_", "atpu_serve_moe_"))]
        assert "atpu_serve_kv_gather" not in found  # the scanned plan's attention reads its pages where they lie
