"""graftlint: every rule must fire on its bad fixture and stay silent on the
good twin, suppressions and the baseline must filter, and the CLI must run
clean over the real package fast enough to live inside `make test`."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from accelerate_tpu.analysis import (
    get_rules,
    load_baseline,
    run_analysis,
    write_baseline,
)

pytestmark = pytest.mark.graftlint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRAFTLINT = os.path.join(REPO, "tools", "graftlint.py")


def lint(tmp_path, source, rule=None, name="snippet.py"):
    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    rules = get_rules([rule]) if rule else None
    return run_analysis([str(f)], rules=rules)


def write_pkg(tmp_path, files, pkg="pkg"):
    """Materialize a multi-file fixture *package* ({relpath: source})."""
    root = tmp_path / pkg
    for rel, source in files.items():
        f = root / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(source))
    if not (root / "__init__.py").exists():
        (root / "__init__.py").write_text("")
    return root


def lint_pkg(tmp_path, files, rule=None, cross_module=True, cache_dir=None):
    root = write_pkg(tmp_path, files)
    rules = get_rules([rule]) if rule else None
    return run_analysis(
        [str(root)], rules=rules, cross_module=cross_module, cache_dir=cache_dir
    )


# ---------------------------------------------------------------------------
# good/bad fixture pairs, one per rule
# ---------------------------------------------------------------------------

FIXTURES = {
    "host-sync-in-trace": (
        """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            y = x.item()          # host transfer inside trace
            z = np.asarray(x)     # numpy concretization inside trace
            return float(x)       # python-scalar cast inside trace
        """,
        3,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            return jnp.asarray(x) * 2   # device op: trace-safe

        def report(loss):
            return float(loss.item())   # eager host code: not traced
        """,
    ),
    "recompile-hazard": (
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def pad(x, n):
            if n:                       # concretizes the tracer
                x = x + 1
            return jnp.zeros((n, 4))    # traced value as a shape
        """,
        2,
        """
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnums=(1,))
        def pad(x, n):
            if n:
                x = x + 1
            return jnp.zeros((n, 4))
        """,
    ),
    "axis-name-mismatch": (
        """
        import jax
        from jax.sharding import Mesh, PartitionSpec as P
        import numpy as np

        mesh = Mesh(np.array(jax.devices()), ("dp", "tp"))

        def allreduce(x):
            return jax.lax.psum(x, "batch")      # mesh has no 'batch'

        spec = P("model", None)                  # nor 'model'
        """,
        2,
        """
        import jax
        from jax.sharding import Mesh, PartitionSpec as P
        import numpy as np

        mesh = Mesh(np.array(jax.devices()), ("dp", "tp"))

        def allreduce(x):
            return jax.lax.psum(x, ("dp", "tp"))

        spec = P("dp", None)
        """,
    ),
    "donation-reuse": (
        """
        import jax

        def f(a):
            return a + 1

        g = jax.jit(f, donate_argnums=(0,))

        def train(x):
            y = g(x)
            return x + y      # x's buffer was donated to g
        """,
        1,
        """
        import jax

        def f(a):
            return a + 1

        g = jax.jit(f, donate_argnums=(0,))

        def train(x):
            x = g(x)          # rebinding the name is the blessed pattern
            return x
        """,
    ),
    "transitive-donation": (
        """
        import jax

        _HISTORY = []

        def f(a):
            return a + 1

        g = jax.jit(f, donate_argnums=(0,))

        def remember(x):
            _HISTORY.append(x)      # alias escapes into module state

        def train(x):
            remember(x)
            x = g(x)                # donation frees the stored alias
            return x
        """,
        1,
        """
        import jax

        _HISTORY = []

        def f(a):
            return a + 1

        g = jax.jit(f, donate_argnums=(0,))

        def remember(x):
            _HISTORY.append(x.copy())   # a copy escapes, not the buffer

        def train(x):
            remember(x)
            x = g(x)
            return x
        """,
    ),
    "dtype-widen": (
        """
        import jax
        import jax.numpy as jnp
        from accelerate_tpu.parallel.compress import quantize

        def make():
            jax.config.update("jax_enable_x64", True)
            return jnp.zeros((4,), dtype=jnp.float64)

        def ship(g):
            payload, scales = quantize(g, 0)
            return payload.astype(jnp.float32)   # scales discarded
        """,
        3,
        """
        import jax.numpy as jnp
        from accelerate_tpu.parallel.compress import dequantize, quantize

        def make():
            return jnp.zeros((4,), dtype=jnp.float32)

        def ship(g):
            payload, scales = quantize(g, 0)
            return dequantize(payload, scales)
        """,
    ),
    "blocking-in-hot-loop": (
        """
        def train(step, batches):
            for b in batches:
                out = step(b)
                out.block_until_ready()     # drains the dispatch queue
            return out
        """,
        1,
        """
        def train(step, batches, profile_every=0):
            for i, b in enumerate(batches):
                out = step(b)
                if profile_every and i % profile_every == 0:
                    out.block_until_ready()  # profiling guard: allowed
            out.block_until_ready()          # after the loop: allowed
            return out
        """,
    ),
    "pallas-hazard": (
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            if x_ref[0, 0] > 0:          # python branch on a ref param
                o_ref[:] = x_ref[:] * 2.0
            print("traced!")             # host print in a kernel body

        def call(x):
            return pl.pallas_call(       # no interpret= / gated fallback
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """,
        3,
        """
        import functools
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref, *, scale):
            if scale > 1:                 # static (kw-only) config: fine
                pl.debug_print("x00 = {}", x_ref[0, 0])
            o_ref[:] = jnp.where(x_ref[:] > 0, x_ref[:] * scale, 0.0)

        def call(x, policy_interpret):
            return pl.pallas_call(
                functools.partial(kernel, scale=2.0),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=policy_interpret,   # policy-threaded lowering
            )(x)
        """,
    ),
    "stage-boundary-vs-plan": (
        """
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        def stage_spans(mesh, num_layers):
            pp = mesh.shape.get("pp", 1)      # axis rediscovery
            per_stage = num_layers // pp      # hand-sliced layer span
            spec = PartitionSpec("pp")        # literal pp layout
            return [
                (s * per_stage, (s + 1) * per_stage) for s in range(pp)
            ], spec

        def ring_hop(x, axis_name="pp"):      # pp-defaulted parameter
            return x

        def step(params, layer_order):
            # in-program stacked-layer permutation: gathers (1-1/V) of the
            # stack EVERY step instead of committing the layout at prepare()
            stacked = jnp.take(params["w"], layer_order, axis=0)
            inverse = jnp.argsort(layer_order)
            return stacked, inverse
        """,
        6,
        """
        def stage_spans(plan, num_layers):
            # the resolved ParallelPlan owns stage boundaries and the pp
            # axis (docs/parallel_plan.md)
            return plan.stage.layer_spans(num_layers), plan.pp

        def step(params):
            # layout committed once at prepare() (§layout contract):
            # the captured body consumes the stack in place
            return params["w"]
        """,
    ),
    # the PR-13 serving-signal deadlock shape: a rank-local telemetry record
    # read guards fleet.resize (only ranks whose local queue is deep enter
    # the collective resize), plus the classic main-process early return
    # before a barrier
    "collective-divergence": (
        """
        from accelerate_tpu.utils import telemetry


        def autoscale(fleet):
            record = telemetry.serving_signal()
            if record and record.get("queue_depth", 0) > 8:
                fleet.resize(2)


        def drain(state):
            if state.is_main_process:
                return None
            state.wait_for_everyone()
        """,
        2,
        """
        from accelerate_tpu.utils import telemetry
        from accelerate_tpu.utils.operations import gather_object


        def agree_depth(values):
            return max(values)


        def autoscale(fleet):
            record = telemetry.serving_signal()
            local_depth = record.get("queue_depth", 0) if record else 0
            # rank-symmetric rewrite: every rank sees every rank's depth,
            # so the resize guard agrees everywhere
            depths = gather_object([local_depth])
            if agree_depth(depths) > 8:
                fleet.resize(2)


        def drain(state):
            state.wait_for_everyone()
            if state.is_main_process:
                return "drained"
            return None
        """,
    ),
}


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_fires_on_bad_fixture(tmp_path, rule):
    bad, expected, _ = FIXTURES[rule]
    res = lint(tmp_path, bad, rule=rule)
    assert len(res.new_findings) == expected, [f.render() for f in res.new_findings]
    assert all(f.rule == rule for f in res.new_findings)


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_silent_on_good_twin(tmp_path, rule):
    _, _, good = FIXTURES[rule]
    res = lint(tmp_path, good, rule=rule)
    assert res.new_findings == [], [f.render() for f in res.new_findings]


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_good_twin_clean_under_all_rules(tmp_path, rule):
    """The good fixtures must not trip *other* rules either."""
    _, _, good = FIXTURES[rule]
    res = lint(tmp_path, good)
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_shape_control_flow_is_trace_static(tmp_path):
    """`if x.shape[0] > 2:` inside jit is legal (shapes are static at trace
    time) and must not trip recompile-hazard."""
    res = lint(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            if x.shape[0] > 2:
                x = x[:2]
            return jnp.zeros((x.shape[0], 4))
        """,
        rule="recompile-hazard",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_serving_entry_raw_length_fires(tmp_path):
    """Serving bucketing contract (docs/serving.md): a raw request-length
    shape (`len(req.prompt)`-shaped arg) flowing into a captured serving
    entry compiles one program per distinct length — recompile-hazard
    fires when no bucket/pad evidence appears in the call."""
    res = lint(
        tmp_path,
        """
        import numpy as np
        from accelerate_tpu.serving.engine import run_prefill

        def serve(pools, g, layers, req):
            ids = np.asarray(req.prompt, np.int32)[None]
            return run_prefill(*pools, g, layers, ids, req.row,
                               len(req.prompt), req.rng)
        """,
        rule="recompile-hazard",
    )
    assert len(res.new_findings) == 1
    assert "bucket" in res.new_findings[0].message


def test_serving_entry_bucketed_is_silent(tmp_path):
    """The good twin: the ids ride through the bucketing helper (and a
    pad-named intermediate) — the TRUE length may still flow raw, it is a
    traced scalar, not a shape."""
    res = lint(
        tmp_path,
        """
        import numpy as np
        from accelerate_tpu.serving import bucket_length
        from accelerate_tpu.serving.engine import run_prefill

        def serve(pools, g, layers, req):
            bucket_len = bucket_length(len(req.prompt), 32)
            padded_ids = np.zeros((1, bucket_len), np.int32)
            padded_ids[0, : len(req.prompt)] = req.prompt
            return run_prefill(*pools, g, layers, padded_ids, req.row,
                               len(req.prompt), req.rng)
        """,
        rule="recompile-hazard",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_aot_deserialize_without_fingerprint_fires(tmp_path):
    """AOT cache-key contract (docs/aot_cache.md): deserialize_and_load
    skips trace AND compile, so nothing below the caller re-validates the
    stored program against this process — loading without a fingerprint
    check in scope dispatches a wrong program on any topology/jax-version
    drift.  recompile-hazard fires."""
    res = lint(
        tmp_path,
        """
        import pickle
        from jax.experimental import serialize_executable

        def load_program(path):
            with open(path, "rb") as f:
                entry = pickle.load(f)
            return serialize_executable.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"]
            )
        """,
        rule="recompile-hazard",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert "fingerprint" in res.new_findings[0].message


def test_aot_deserialize_with_fingerprint_check_silent(tmp_path):
    """The good twin: the entry's stored fingerprint is compared against the
    live topology before the executable loads — stale entries fall through
    to a normal compile instead of dispatching."""
    res = lint(
        tmp_path,
        """
        import pickle
        from jax.experimental import serialize_executable

        def load_program(path, live_topology):
            with open(path, "rb") as f:
                entry = pickle.load(f)
            if entry["fingerprint"] != live_topology:
                return None  # stale: caller compiles normally
            return serialize_executable.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"]
            )
        """,
        rule="recompile-hazard",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_blocking_in_while_test_is_flagged(tmp_path):
    """A While test re-evaluates every iteration — a blocking call there is
    a per-step sync, same as in the body."""
    res = lint(
        tmp_path,
        """
        def converge(state, step):
            while not state.done.block_until_ready():
                state = step(state)
            return state
        """,
        rule="blocking-in-hot-loop",
    )
    assert len(res.new_findings) == 1


def test_profiler_session_in_loop_fires(tmp_path):
    """jax.profiler start/stop_trace per loop iteration opens a global trace
    session every step — the blocking-in-hot-loop profiler extension."""
    res = lint(
        tmp_path,
        """
        import jax

        def train(step, batches):
            for b in batches:
                jax.profiler.start_trace("/tmp/t")
                out = step(b)
                jax.profiler.stop_trace()
            return out
        """,
        rule="blocking-in-hot-loop",
    )
    assert len(res.new_findings) == 2, [f.render() for f in res.new_findings]
    assert all("sample" in f.message for f in res.new_findings)


def test_profiler_session_knob_guard_alone_still_fires(tmp_path):
    """A profiling-knob guard exempts a plain sync, but NOT a trace
    session: `if profiling:` is what turns the every-step session on —
    only sampled-cadence evidence exempts start/stop_trace."""
    res = lint(
        tmp_path,
        """
        import jax

        def train(step, batches, profiling=False):
            for b in batches:
                if profiling:
                    jax.profiler.start_trace("/tmp/t")
                out = step(b)
                if profiling:
                    jax.profiler.stop_trace()
            return out
        """,
        rule="blocking-in-hot-loop",
    )
    assert len(res.new_findings) == 2, [f.render() for f in res.new_findings]


def test_profiler_session_sampled_cadence_is_silent(tmp_path):
    """The good twin: the session opens only on the sampled iteration —
    a modulus test (or cadence-named predicate) is the evidence, matching
    the telemetry profile_every_n pattern."""
    res = lint(
        tmp_path,
        """
        import jax

        def train(step, batches, profile_every_n=0):
            for i, b in enumerate(batches):
                sampled = profile_every_n and i % profile_every_n == 0
                if sampled:
                    jax.profiler.start_trace("/tmp/t")
                out = step(b)
                if sampled:
                    jax.profiler.stop_trace()
            return out
        """,
        rule="blocking-in-hot-loop",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_payload_astype_suppressed_inside_compression_layer(tmp_path):
    """Policy-scoped suppression: the compression layer ITSELF is the
    sanctioned quantize/dequantize boundary, so payload casts inside
    ``parallel/compress.py`` never fire — by rule scope, not by inline
    comments (the good/bad pair in FIXTURES covers the outside-the-layer
    case)."""
    source = """
        import jax.numpy as jnp

        def quantize(x, axis):
            return x.astype(jnp.int8), jnp.ones((1,))

        def dequantize(payload, scales):
            payload, scales = quantize(payload, 0)
            return payload.astype(jnp.float32) * scales
        """
    res = lint_pkg(
        tmp_path, {"parallel/compress.py": source}, rule="dtype-widen"
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]
    # the SAME source outside the policy module fires (local quantize defs
    # don't resolve to compress.quantize, so give it the real import)
    outside = """
        import jax.numpy as jnp
        from pkg.parallel.compress import quantize

        def widen(x):
            payload, scales = quantize(x, 0)
            return payload.astype(jnp.float32)
        """
    res = lint_pkg(
        tmp_path,
        {"parallel/compress.py": source, "user.py": outside},
        rule="dtype-widen",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert "user.py" in res.new_findings[0].path


def test_payload_tracking_is_scope_aware(tmp_path):
    """A same-named local in an UNRELATED function is not the payload; an
    outer-scope payload cast inside a nested closure still is (once)."""
    res = lint(
        tmp_path,
        """
        import jax.numpy as jnp
        from accelerate_tpu.parallel.compress import quantize

        def compresses(g):
            payload, scales = quantize(g, 0)
            return payload, scales

        def unrelated(buf):
            payload = buf.view()
            return payload.astype(jnp.float32)   # not a wire payload
        """,
        rule="dtype-widen",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]
    res = lint(
        tmp_path,
        """
        import jax.numpy as jnp
        from accelerate_tpu.parallel.compress import quantize

        def outer(g):
            payload, scales = quantize(g, 0)

            def widen():
                return payload.astype(jnp.float32)   # closure over the payload

            return widen()
        """,
        name="closure.py",
        rule="dtype-widen",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


def test_payload_astype_via_module_alias_fires(tmp_path):
    """``from ..parallel import compress`` + ``compress.quantize`` resolves
    through the alias map the same as a from-import of the function."""
    res = lint(
        tmp_path,
        """
        import jax.numpy as jnp
        from accelerate_tpu.parallel import compress

        def widen(g):
            w = compress.quantize(g, 0)
            return w.astype(jnp.float32)
        """,
        rule="dtype-widen",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------

def test_same_line_suppression(tmp_path):
    res = lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            return x.item()  # graftlint: disable=host-sync-in-trace
        """,
        rule="host-sync-in-trace",
    )
    assert res.new_findings == []
    assert res.suppressed == 1


def test_preceding_line_suppression(tmp_path):
    res = lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            # graftlint: disable=host-sync-in-trace
            return x.item()
        """,
        rule="host-sync-in-trace",
    )
    assert res.new_findings == []
    assert res.suppressed == 1


def test_suppression_is_per_rule(tmp_path):
    """Disabling one rule must not silence another on the same line."""
    res = lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            return x.item()  # graftlint: disable=dtype-widen
        """,
        rule="host-sync-in-trace",
    )
    assert len(res.new_findings) == 1


def test_suppression_tolerates_justification_text(tmp_path):
    """Project policy requires a justification after the rule id — it must
    not break the rule-name parse."""
    res = lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            return x.item()  # graftlint: disable=host-sync-in-trace -- demo of policy-mandated justification
        """,
        rule="host-sync-in-trace",
    )
    assert res.new_findings == []
    assert res.suppressed == 1


def test_docstring_mentioning_syntax_does_not_suppress(tmp_path):
    """Only real comments suppress; prose in a docstring that documents the
    syntax must not disable rules for the file."""
    res = lint(
        tmp_path,
        '''
        """Docs: silence a rule with `# graftlint: disable-file=host-sync-in-trace`."""
        import jax

        @jax.jit
        def step(x):
            return x.item()
        ''',
        rule="host-sync-in-trace",
    )
    assert len(res.new_findings) == 1


def test_file_level_suppression(tmp_path):
    res = lint(
        tmp_path,
        """
        # graftlint: disable-file=host-sync-in-trace
        import jax

        @jax.jit
        def step(x):
            return x.item()
        """,
        rule="host-sync-in-trace",
    )
    assert res.new_findings == []


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def test_baseline_grandfathers_existing_findings(tmp_path):
    bad, _, _ = FIXTURES["donation-reuse"]
    f = tmp_path / "legacy.py"
    f.write_text(textwrap.dedent(bad))
    first = run_analysis([str(f)])
    assert first.new_findings
    baseline_path = tmp_path / "baseline.json"
    write_baseline(first.findings, str(baseline_path))
    again = run_analysis([str(f)], baseline=load_baseline(str(baseline_path)))
    assert again.new_findings == []       # baselined
    assert len(again.findings) == len(first.findings)  # still detected


def test_baseline_survives_line_drift_but_not_new_findings(tmp_path):
    bad, _, _ = FIXTURES["donation-reuse"]
    f = tmp_path / "legacy.py"
    f.write_text(textwrap.dedent(bad))
    baseline_path = tmp_path / "baseline.json"
    write_baseline(run_analysis([str(f)]).findings, str(baseline_path))
    # unrelated edit above shifts every line; old finding stays baselined,
    # the fresh violation (a new symbol) is reported
    f.write_text(
        "HEADER = 1\n"
        + textwrap.dedent(bad)
        + textwrap.dedent(
            """
            def train2(x):
                y = g(x)
                return x + y
            """
        )
    )
    res = run_analysis([str(f)], baseline=load_baseline(str(baseline_path)))
    assert len(res.new_findings) == 1
    assert res.new_findings[0].symbol == "train2"


def test_unknown_rule_id_raises():
    with pytest.raises(KeyError):
        get_rules(["not-a-rule"])


# ---------------------------------------------------------------------------
# CLI (subprocess: the exact invocation `make lint` runs)
# ---------------------------------------------------------------------------

def _run_cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, GRAFTLINT, *args],
        cwd=cwd,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_cli_exits_nonzero_with_findings(tmp_path):
    bad, _, _ = FIXTURES["blocking-in-hot-loop"]
    (tmp_path / "bad.py").write_text(textwrap.dedent(bad))
    proc = _run_cli(str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "blocking-in-hot-loop" in proc.stdout


def test_cli_json_output(tmp_path):
    bad, _, _ = FIXTURES["dtype-widen"]
    (tmp_path / "bad.py").write_text(textwrap.dedent(bad))
    proc = _run_cli(str(tmp_path), "--format", "json")
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert data["files_analyzed"] == 1
    assert {f["rule"] for f in data["findings"]} == {"dtype-widen"}
    assert all("fingerprint" in f for f in data["findings"])


def test_cli_write_then_use_baseline(tmp_path):
    bad, _, _ = FIXTURES["donation-reuse"]
    (tmp_path / "bad.py").write_text(textwrap.dedent(bad))
    baseline = tmp_path / "baseline.json"
    assert _run_cli(str(tmp_path), "--write-baseline", str(baseline)).returncode == 0
    proc = _run_cli(str(tmp_path), "--baseline", str(baseline))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for rule in FIXTURES:
        assert rule in proc.stdout


def test_package_is_clean_and_fast():
    """Acceptance gate: the real package lints clean under COLD whole-program
    analysis (no cache), within the <15 s budget that lets `make lint-cold`
    sit in CI in front of every `make test`."""
    proc = _run_cli("accelerate_tpu", "--format", "json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert data["findings"] == []
    assert data["cross_module"] is True
    assert data["files_analyzed"] > 100
    assert data["duration_s"] < 15.0, f"analysis took {data['duration_s']}s"


_INDEX_SNIPPET = """
import functools

@functools.partial(jit, static_argnums=(0,))
def outer(n, x=make(), *, y=other()):
    z = [v * 2 for v in x if v]

    @decorate(n)
    def inner(a, b=default_of_inner()):
        return helper(a) + lambda_user(lambda q: q + b)

    class Local(Base):
        attr = value()

        def method(self):
            return self.attr

    if n > 1:
        return inner(z) + Local().method()
    return {k: v for k, v in zip(x, z)}[0]
"""


def _own_by_stack(fn_node):
    """A scope's own nodes by the plain stack walk: its body short of
    nested def/class bodies, with their decorators and defaults."""
    import ast

    stack, out = list(ast.iter_child_nodes(fn_node)), []
    while stack:
        node = stack.pop()
        out.append(node)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            stack.extend(node.decorator_list)
            if not isinstance(node, ast.ClassDef):
                stack.extend(node.args.defaults + [d for d in node.args.kw_defaults if d])
            continue
        stack.extend(ast.iter_child_nodes(node))
    return out


def test_module_index_walks_what_ast_walk_does(tmp_path):
    """The one walk of a module that every rule and the summary read:
    ``walk`` yields what ``ast.walk`` does (in source order; ``as_walked``
    gives back ast.walk's order), ``of_type`` and ``walk(node, *types)``
    select by type, and ``own`` is a scope's body short of nested def/class
    bodies, with their decorators and defaults."""
    import ast

    from accelerate_tpu.analysis.engine import ModuleInfo

    f = tmp_path / "snippet.py"
    f.write_text(_INDEX_SNIPPET)
    mod = ModuleInfo(str(f), "snippet.py", _INDEX_SNIPPET)
    index = mod.index

    def own_nodes(nodes):  # the parser shares expression contexts and operators
        return [n for n in nodes if isinstance(n, (ast.stmt, ast.expr, ast.arg, ast.keyword))]

    for node in own_nodes(ast.walk(mod.tree)):
        walked = own_nodes(ast.walk(node))
        assert index.as_walked(own_nodes(index.walk(node))) == walked
        assert index.walk(node, ast.Call, ast.Name) == [
            n for n in index.walk(node) if isinstance(n, (ast.Call, ast.Name))
        ]
    assert index.of_type(ast.Call) == [n for n in index.walk(mod.tree) if isinstance(n, ast.Call)]
    scopes = [mod.tree] + index.of_type(ast.FunctionDef, ast.ClassDef)
    assert [s.name for s in scopes[1:]] == ["outer", "inner", "Local", "method"]
    for scope in scopes:
        want = own_nodes(_own_by_stack(scope))
        got = own_nodes(index.own(scope))
        assert sorted(map(id, got)) == sorted(map(id, want)), getattr(scope, "name", "<module>")
        assert index.own(scope, ast.Call) == [n for n in got if isinstance(n, ast.Call)]
    outer = scopes[1]
    calls = {n.func.id for n in index.own(outer, ast.Call) if isinstance(n.func, ast.Name)}
    # the nested def's decorator and default run in outer's scope; its body does not
    assert {"decorate", "default_of_inner", "make", "other", "zip"} <= calls
    assert not {"helper", "lambda_user", "value"} & calls


# ---------------------------------------------------------------------------
# donation-reuse: loop second pass (use-after-donate across iterations)
# ---------------------------------------------------------------------------

LOOP_DONATION_BAD = """
import jax

step = jax.jit(lambda s: s * 2, donate_argnums=(0,))

def train(state, batches):
    for batch in batches:
        report(state)        # fine on iteration 1, dead buffer on iteration 2
        out = step(state)    # donates `state` without rebinding it
    return out
"""

LOOP_DONATION_GOOD = """
import jax

step = jax.jit(lambda s: s * 2, donate_argnums=(0,))

def train(state, batches):
    for batch in batches:
        report(state)        # rebind below makes iteration 2 read live data
        state = step(state)
    return state
"""

LOOP_DONATION_WHILE_BAD = """
import jax

step = jax.jit(lambda s: s, donate_argnums=(0,))

def train(state):
    while state_norm(state) > 1.0:   # the TEST reads the donated buffer too
        _ = step(state)
    return None
"""


def test_donation_loop_carried_reuse_is_flagged(tmp_path):
    res = lint(tmp_path, LOOP_DONATION_BAD, rule="donation-reuse")
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert "state" in res.new_findings[0].message


def test_donation_loop_rebind_is_clean(tmp_path):
    res = lint(tmp_path, LOOP_DONATION_GOOD, rule="donation-reuse")
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_donation_while_test_reuse_is_flagged(tmp_path):
    res = lint(tmp_path, LOOP_DONATION_WHILE_BAD, rule="donation-reuse")
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


def test_donation_straight_line_in_loop_reported_once(tmp_path):
    """The second pass must not duplicate findings the linear scan already
    reported."""
    src = """
    import jax

    step = jax.jit(lambda s: s, donate_argnums=(0,))

    def train(state, batches):
        for batch in batches:
            out = step(state)
            loss = state.sum()   # straight-line use-after-donate
            state = out
    """
    res = lint(tmp_path, src, rule="donation-reuse")
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


# ---------------------------------------------------------------------------
# sharding-spec-drift (needs a checkpoint index to compare against)
# ---------------------------------------------------------------------------

PLAN_SNIPPET = """
class Model:
    tp_plan = {
        ".*q_proj.weight": ("tp", None),
        ".*mlp.weight": (None, "tp"),
    }
"""


def _write_index(tmp_path, specs, name="model"):
    index = {
        "metadata": {"num_shards": 1},
        "tensors": {
            tensor: {"shape": [8, 8], "dtype": "float32", "spec": spec}
            for tensor, spec in specs.items()
        },
    }
    path = tmp_path / f"{name}.index.json"
    path.write_text(json.dumps(index))
    return str(path)


def _lint_with_index(tmp_path, source, index_path):
    f = tmp_path / "plan.py"
    f.write_text(textwrap.dedent(source))
    return run_analysis(
        [str(f)], rules=get_rules(["sharding-spec-drift"]), ckpt_index=index_path
    )


def test_spec_drift_flags_plan_edit(tmp_path):
    # checkpoint was saved with q_proj sharded ("tp", None); the plan now
    # says (None, "tp") — same axes, different dim: silent step-one reshard
    index = _write_index(
        tmp_path,
        {"layers.0.q_proj.weight": [None, "tp"], "layers.0.mlp.weight": [None, "tp"]},
    )
    res = _lint_with_index(tmp_path, PLAN_SNIPPET, index)
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    f = res.new_findings[0]
    assert f.rule == "sharding-spec-drift"
    assert "q_proj" in f.message


def test_spec_drift_silent_when_plan_matches(tmp_path):
    index = _write_index(
        tmp_path,
        {"layers.0.q_proj.weight": ["tp"], "layers.0.mlp.weight": [None, "tp"]},
    )
    res = _lint_with_index(tmp_path, PLAN_SNIPPET, index)
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_spec_drift_ignores_replicated_record(tmp_path):
    """A fully-replicated record proves nothing (a tp:1 mesh canonicalizes
    every template away) — no finding."""
    index = _write_index(tmp_path, {"layers.0.q_proj.weight": []})
    res = _lint_with_index(tmp_path, PLAN_SNIPPET, index)
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_spec_drift_inert_without_index(tmp_path):
    res = lint(tmp_path, PLAN_SNIPPET, rule="sharding-spec-drift")
    assert res.new_findings == []


def test_spec_drift_cli_ckpt_index(tmp_path):
    index = _write_index(tmp_path, {"layers.0.q_proj.weight": [None, "tp"]})
    (tmp_path / "plan.py").write_text(textwrap.dedent(PLAN_SNIPPET))
    proc = _run_cli(str(tmp_path / "plan.py"), "--ckpt-index", index)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "sharding-spec-drift" in proc.stdout
    # same invocation minus the index: clean
    proc = _run_cli(str(tmp_path / "plan.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_spec_drift_ignores_auto_added_fsdp_axis(tmp_path):
    """plan_param_spec layers "fsdp" onto a template-free dim on fsdp>1
    meshes; a recorded fsdp the template never mentioned is auto-sharding,
    not drift (false-positive regression from review)."""
    index = _write_index(tmp_path, {"layers.0.q_proj.weight": ["tp", "fsdp"]})
    res = _lint_with_index(tmp_path, PLAN_SNIPPET, index)
    assert res.new_findings == [], [f.render() for f in res.new_findings]


# ---------------------------------------------------------------------------
# sharding-spec-drift: plan_param_spec strategy drift (fsdp-sharded
# checkpoint vs a source strategy that no longer shards)
# ---------------------------------------------------------------------------

STRATEGY_SNIPPET = """
from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

plugin = FullyShardedDataParallelPlugin(sharding_strategy={strategy!r})
"""


def test_strategy_drift_flags_no_shard_against_fsdp_checkpoint(tmp_path):
    index = _write_index(tmp_path, {"layers.0.mlp.weight": ["fsdp", None]})
    res = _lint_with_index(
        tmp_path, STRATEGY_SNIPPET.format(strategy="NO_SHARD"), index
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    f = res.new_findings[0]
    assert "NO_SHARD" in f.message and "mlp" in f.message


def test_strategy_drift_silent_when_still_sharding(tmp_path):
    index = _write_index(tmp_path, {"layers.0.mlp.weight": ["fsdp", None]})
    res = _lint_with_index(
        tmp_path, STRATEGY_SNIPPET.format(strategy="FULL_SHARD"), index
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_strategy_drift_silent_without_fsdp_record(tmp_path):
    """A checkpoint with no fsdp axis recorded proves nothing — it may have
    been saved on an fsdp:1 mesh, which canonicalizes the axis away."""
    index = _write_index(tmp_path, {"layers.0.mlp.weight": ["tp", None]})
    res = _lint_with_index(
        tmp_path, STRATEGY_SNIPPET.format(strategy="NO_SHARD"), index
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


# ---------------------------------------------------------------------------
# whole-program mode: cross-module reachability (tentpole)
# ---------------------------------------------------------------------------

CROSS_HOST_SYNC_BAD = {
    "ops.py": """
        import jax
        from .helpers import summarize

        @jax.jit
        def step(x):
            return summarize(x)
        """,
    "helpers.py": """
        def summarize(x):
            return float(x.mean())      # host sync, traced via ops.step
        """,
}

CROSS_HOST_SYNC_GOOD = {
    "ops.py": CROSS_HOST_SYNC_BAD["ops.py"],
    "helpers.py": """
        def summarize(x):
            return x.mean() * 2         # device op: trace-safe
        """,
}


def test_cross_module_host_sync_fires_in_whole_program_mode(tmp_path):
    """Acceptance fixture: a traced ops/-style module calls a host-syncing
    helper in a utils/-style module — visible only to the whole-program
    graph."""
    res = lint_pkg(tmp_path, CROSS_HOST_SYNC_BAD, rule="host-sync-in-trace")
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    f = res.new_findings[0]
    assert f.path.endswith("helpers.py") and f.symbol == "summarize"
    assert "ops.py" in f.message  # the reason names the traced caller


def test_cross_module_host_sync_silent_without_whole_program(tmp_path):
    """Same bad package with --no-cross-module: the per-module graph cannot
    see the import edge, so nothing fires (the historical behavior)."""
    res = lint_pkg(
        tmp_path, CROSS_HOST_SYNC_BAD, rule="host-sync-in-trace", cross_module=False
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]
    assert res.cross_module is False


def test_cross_module_host_sync_good_twin_clean(tmp_path):
    res = lint_pkg(tmp_path, CROSS_HOST_SYNC_GOOD)
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_reexport_chain_reachability(tmp_path):
    """`from . import stat` where pkg/__init__.py re-exports stat from a
    submodule: the chain __init__ → helpers must resolve."""
    res = lint_pkg(
        tmp_path,
        {
            "__init__.py": "from .helpers import stat\n",
            "helpers.py": """
                def stat(x):
                    return x.item()
                """,
            "ops.py": """
                import jax
                from . import stat

                @jax.jit
                def step(x):
                    return stat(x)
                """,
        },
        rule="host-sync-in-trace",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].path.endswith("helpers.py")


INSTANCE_DISPATCH_BAD = {
    "impl.py": """
        class Runner:
            def work(self, x):
                return x.item()        # host sync, reached via r.work(x)
        """,
    "ops.py": """
        import jax
        from .impl import Runner

        @jax.jit
        def step(x):
            r = Runner()
            return r.work(x)
        """,
}

INSTANCE_DISPATCH_GOOD = {
    "impl.py": """
        class Runner:
            def work(self, x):
                return x.item()
        """,
    "ops.py": """
        import jax
        from .impl import Runner

        def other():
            return object()

        @jax.jit
        def step(x):
            r = Runner()
            r = other()            # reassigned: type no longer inferable
            return r.work(x)
        """,
}


def test_instance_method_dispatch_resolves_across_modules(tmp_path):
    """ANALYSIS_VERSION 7 fixture: `obj = SomeClass(); obj.method(x)` with
    the class imported from another module — cheap type inference over the
    single-assignment local links the traced caller to the method."""
    res = lint_pkg(tmp_path, INSTANCE_DISPATCH_BAD, rule="host-sync-in-trace")
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    f = res.new_findings[0]
    assert f.path.endswith("impl.py") and f.symbol == "Runner.work"
    assert "ops.py" in f.message  # the reason names the traced caller


def test_instance_method_dispatch_reassigned_receiver_silent(tmp_path):
    """The good twin: a receiver bound more than once has no inferable type
    — the edge must NOT be created (a wrong guess would cross-wire
    reachability into unrelated classes)."""
    res = lint_pkg(tmp_path, INSTANCE_DISPATCH_GOOD, rule="host-sync-in-trace")
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_instance_method_dispatch_factory_function_not_a_class(tmp_path):
    """Review-pinned: a factory FUNCTION with a nested def owns
    `factory.inner` qualnames too — it must NOT be treated as a class, or
    `obj = make_helper(); obj.compute(x)` would wire a phantom edge into
    the unrelated nested function (same- and cross-module)."""
    files = {
        "impl.py": """
            def make_helper():
                def compute(x):
                    return x.item()     # nested def, NOT a method
                return object()
            """,
        "ops.py": """
            import jax
            from .impl import make_helper

            @jax.jit
            def step(x):
                obj = make_helper()
                return obj.compute(x)
            """,
    }
    res = lint_pkg(tmp_path, files, rule="host-sync-in-trace")
    assert res.new_findings == [], [f.render() for f in res.new_findings]
    # same-module twin
    res2 = lint(
        tmp_path,
        """
        import jax

        def make_helper():
            def compute(x):
                return x.item()
            return object()

        @jax.jit
        def step(x):
            obj = make_helper()
            return obj.compute(x)
        """,
        rule="host-sync-in-trace",
    )
    assert res2.new_findings == [], [f.render() for f in res2.new_findings]


def test_instance_method_dispatch_same_module(tmp_path):
    """Same-module form: the `Cls.method` edge resolves by exact qualname
    (no leaf-name collision with free functions named like the method)."""
    res = lint(
        tmp_path,
        """
        import jax

        class Runner:
            def work(self, x):
                return x.item()

        def work(y):               # same-named free function: must NOT fire
            return y + 1

        @jax.jit
        def step(x):
            r = Runner()
            return r.work(x)
        """,
        rule="host-sync-in-trace",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].symbol == "Runner.work"


INSTANCE_DISPATCH_REBOUND_SAME_BAD = {
    "impl.py": """
        class Runner:
            def __init__(self, opts=None):
                self.opts = opts

            def work(self, x):
                return x.item()        # host sync, reached via r.work(x)
        """,
    "ops.py": """
        import jax
        from .impl import Runner

        @jax.jit
        def step(x, fast):
            if fast:
                r = Runner()
            else:
                r = Runner({"slow": True})   # rebound — SAME class
            return r.work(x)
        """,
}

INSTANCE_DISPATCH_REBOUND_MIXED_GOOD = {
    "impl.py": """
        class Runner:
            def work(self, x):
                return x.item()
        """,
    "ops.py": """
        import jax
        from .impl import Runner

        class Other:
            def work(self, x):
                return x + 1

        @jax.jit
        def step(x, fast):
            if fast:
                r = Runner()
            else:
                r = Other()            # rebound to a DIFFERENT class
            return r.work(x)
        """,
}


def test_instance_dispatch_joins_over_branches_same_class(tmp_path):
    """ANALYSIS_VERSION 9 fixture (ROADMAP carried item): a receiver
    rebound across branches to the SAME class is still that class — the
    join of identical types — so `r.work(x)` links to Runner.work and the
    traced host sync fires."""
    res = lint_pkg(
        tmp_path, INSTANCE_DISPATCH_REBOUND_SAME_BAD, rule="host-sync-in-trace"
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    f = res.new_findings[0]
    assert f.path.endswith("impl.py") and f.symbol == "Runner.work"


def test_instance_dispatch_rebound_different_classes_silent(tmp_path):
    """The good twin: branches binding DIFFERENT classes have no single
    join type — the edge must NOT be created (a wrong guess would
    cross-wire reachability into whichever class happened to list first)."""
    res = lint_pkg(
        tmp_path, INSTANCE_DISPATCH_REBOUND_MIXED_GOOD, rule="host-sync-in-trace"
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


FACTORY_RETURN_DISPATCH_BAD = {
    "impl.py": """
        class Runner:
            def __init__(self, opts=None):
                self.opts = opts

            def work(self, x):
                return x.item()        # host sync, reached via the factory
        """,
    "ops.py": """
        import jax
        from .impl import Runner

        def make_runner(fast=True):
            if fast:
                return Runner()
            return Runner({"slow": True})   # every return: SAME class

        @jax.jit
        def step(x):
            r = make_runner()
            return r.work(x)
        """,
}

FACTORY_RETURN_DISPATCH_MIXED_GOOD = {
    "impl.py": """
        class Runner:
            def work(self, x):
                return x.item()
        """,
    "ops.py": """
        import jax
        from .impl import Runner

        class Other:
            def work(self, x):
                return x + 1

        def make_runner(fast=True):
            if fast:
                return Runner()
            return Other()             # mixed classes: no single return type

        def make_opaque(cfg):
            if cfg:
                return Runner()
            return cfg                 # non-constructor return

        @jax.jit
        def step(x, cfg):
            r = make_runner()
            s = make_opaque(cfg)
            return r.work(x) + s.work(x)
        """,
}


def test_instance_dispatch_through_factory_returns(tmp_path):
    """ANALYSIS_VERSION 10 fixture (ROADMAP carried item): a receiver bound
    from a function whose returns are ALL `SomeClass(...)` constructors of
    one class resolves to SomeClass.method — `r = make_runner(); r.work(x)`
    reaches Runner.work and the traced host sync fires."""
    res = lint_pkg(
        tmp_path, FACTORY_RETURN_DISPATCH_BAD, rule="host-sync-in-trace"
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    f = res.new_findings[0]
    assert f.path.endswith("impl.py") and f.symbol == "Runner.work"


def test_instance_dispatch_factory_mixed_returns_silent(tmp_path):
    """The good twin: a factory whose branches construct DIFFERENT classes
    — or return a non-constructor value — has no single return type, so
    the receiver stays uninferred and nothing fires."""
    res = lint_pkg(
        tmp_path, FACTORY_RETURN_DISPATCH_MIXED_GOOD, rule="host-sync-in-trace"
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_instance_dispatch_factory_shadowed_or_method_silent(tmp_path):
    """Review-pinned guards on the v10 factory map: (1) a PARAMETER named
    like a module factory is data — any callable could be injected, so the
    receiver must stay uninferred; (2) a METHOD (or nested def) sharing a
    factory-shaped body must not enter the bare-name map — `build` is
    never callable as a module-level name."""
    res = lint(
        tmp_path,
        """
        import jax

        class Runner:
            def work(self, x):
                return x.item()

        def make_runner():
            return Runner()

        @jax.jit
        def step(x, make_runner):
            r = make_runner()        # the PARAMETER, not the factory
            return r.work(x)
        """,
        rule="host-sync-in-trace",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]
    res2 = lint(
        tmp_path,
        """
        import jax

        class Runner:
            def work(self, x):
                return x.item()

        class Pool:
            def build(self):
                return Runner()      # a METHOD, not a bare-name factory

        @jax.jit
        def step(x, build):
            r = build()              # unrelated injected callable
            return r.work(x)
        """,
        rule="host-sync-in-trace",
        name="snippet2.py",
    )
    assert res2.new_findings == [], [f.render() for f in res2.new_findings]


def test_instance_dispatch_factory_rebound_or_decorated_silent(tmp_path):
    """Review-pinned guards on the v10 factory map, round 2: (1) a module
    name REBOUND after a qualifying factory def (a later non-factory def
    wins the live binding) must drop the mapping; (2) a DECORATED factory's
    wrapper decides what a call returns (a future, a memo proxy) — the
    body's returns say nothing, so no mapping."""
    res = lint(
        tmp_path,
        """
        import jax

        class Runner:
            def work(self, x):
                return x.item()

        def make():
            return Runner()

        def make():                  # live binding: NOT a factory
            return _singleton

        @jax.jit
        def step(x):
            r = make()
            return r.work(x)
        """,
        rule="host-sync-in-trace",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]
    res2 = lint(
        tmp_path,
        """
        import jax
        from concurrent.futures import ThreadPoolExecutor

        class Runner:
            def work(self, x):
                return x.item()

        def deferred(fn):
            def wrap(*a):
                return ThreadPoolExecutor().submit(fn, *a)
            return wrap

        @deferred
        def make():                  # calling make() returns a Future
            return Runner()

        @jax.jit
        def step(x):
            r = make()
            return r.work(x)
        """,
        rule="host-sync-in-trace",
        name="snippet2.py",
    )
    assert res2.new_findings == [], [f.render() for f in res2.new_findings]


IMPORTED_FACTORY_DISPATCH_BAD = {
    "impl.py": """
        class Runner:
            def work(self, x):
                return x.item()

        def make_runner():
            return Runner()
        """,
    "train.py": """
        import jax
        from .impl import make_runner

        @jax.jit
        def step(x):
            r = make_runner()        # factory IMPORTED from impl
            return r.work(x)
        """,
}


def test_instance_dispatch_through_imported_factory(tmp_path):
    """ANALYSIS_VERSION 11 fixture (ROADMAP carried item): the v10 factory
    map was per-module — a factory IMPORTED single-hop
    (`from .impl import make_runner`) now resolves the receiver to the
    class its returns construct, so the traced host sync in Runner.work
    fires from another module's jitted step."""
    res = lint_pkg(
        tmp_path, IMPORTED_FACTORY_DISPATCH_BAD, rule="host-sync-in-trace"
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    f = res.new_findings[0]
    assert f.path.endswith("impl.py") and f.symbol == "Runner.work"


def test_imported_factory_shadowed_param_silent(tmp_path):
    """The good twin: the imported factory's name rebound as a PARAMETER is
    injected data — any callable could arrive there, so the receiver must
    stay uninferred (the v11 local-shadow guard)."""
    res = lint_pkg(
        tmp_path,
        {
            "impl.py": IMPORTED_FACTORY_DISPATCH_BAD["impl.py"],
            "train.py": """
                import jax
                from .impl import make_runner

                @jax.jit
                def step(x, make_runner):
                    r = make_runner()    # the PARAMETER, not the import
                    return r.work(x)
                """,
        },
        rule="host-sync-in-trace",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_imported_factory_delegation_chain_resolves(tmp_path):
    """v12: a factory that DELEGATES to another factory resolves through the
    chain to the ground class, so dispatch through the imported outer
    factory reaches Runner.work."""
    res = lint_pkg(
        tmp_path,
        {
            "impl.py": """
                class Runner:
                    def work(self, x):
                        return x.item()

                def make_inner():
                    return Runner()

                def make_runner():
                    return make_inner()   # factory -> factory delegation
                """,
            "train.py": """
                import jax
                from .impl import make_runner

                @jax.jit
                def step(x):
                    r = make_runner()
                    return r.work(x)
                """,
        },
        rule="host-sync-in-trace",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].symbol == "Runner.work"
    assert res.new_findings[0].path.endswith("impl.py")


def test_factory_delegation_cycle_silent(tmp_path):
    """Mutually-delegating factories have no ground class: the cycle is
    dropped, never looped over or guessed at."""
    res = lint_pkg(
        tmp_path,
        {
            "impl.py": """
                class Runner:
                    def work(self, x):
                        return x.item()

                def make_a():
                    return make_b()

                def make_b():
                    return make_a()
                """,
            "train.py": """
                import jax
                from .impl import make_a

                @jax.jit
                def step(x):
                    r = make_a()
                    return r.work(x)
                """,
        },
        rule="host-sync-in-trace",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_factory_through_reexport_chain_resolves(tmp_path):
    """Multi-hop: train imports the factory from an api module that
    re-exports it from impl; the returned class still resolves."""
    res = lint_pkg(
        tmp_path,
        {
            "impl.py": """
                class Runner:
                    def work(self, x):
                        return x.item()

                def make_inner():
                    return Runner()

                def make_runner():
                    return make_inner()
                """,
            "api.py": """
                from .impl import make_runner
                """,
            "train.py": """
                import jax
                from .api import make_runner

                @jax.jit
                def step(x):
                    r = make_runner()
                    return r.work(x)
                """,
        },
        rule="host-sync-in-trace",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].symbol == "Runner.work"


def test_factory_mixed_chain_still_silent(tmp_path):
    """A delegation chain whose inner factory returns DIFFERENT classes on
    different paths stays uninferred (silent, never wrong)."""
    res = lint_pkg(
        tmp_path,
        {
            "impl.py": """
                class Runner:
                    def work(self, x):
                        return x.item()

                class Other:
                    def work(self, x):
                        return x

                def make_inner(fast):
                    if fast:
                        return Runner()
                    return Other()

                def make_runner(fast):
                    return make_inner(fast)
                """,
            "train.py": """
                import jax
                from .impl import make_runner

                @jax.jit
                def step(x):
                    r = make_runner(True)
                    return r.work(x)
                """,
        },
        rule="host-sync-in-trace",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_partial_callback_crosses_module_boundary(tmp_path):
    """A partial(...)-wrapped callback handed to lax.scan in another module
    is a trace root there."""
    res = lint_pkg(
        tmp_path,
        {
            "utils.py": """
                def do_step(cfg, carry, x):
                    return carry, x.item()
                """,
            "ops.py": """
                import functools
                import jax
                from .utils import do_step

                def run(xs, cfg):
                    return jax.lax.scan(functools.partial(do_step, cfg), None, xs)
                """,
        },
        rule="host-sync-in-trace",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].symbol == "do_step"


def test_module_alias_call_crosses_boundary(tmp_path):
    """Dotted calls through a module alias (`from . import helpers;
    helpers.summarize(x)`) resolve too."""
    res = lint_pkg(
        tmp_path,
        {
            "helpers.py": CROSS_HOST_SYNC_BAD["helpers.py"],
            "ops.py": """
                import jax
                from . import helpers

                @jax.jit
                def step(x):
                    return helpers.summarize(x)
                """,
        },
        rule="host-sync-in-trace",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


def test_duplicate_module_names_are_not_cross_wired(tmp_path):
    """Two same-stem files outside any package both claim the module name
    'train' — the ambiguous name must resolve to NEITHER, not silently wire
    every import to the first file (review regression: a/train.py's host
    sync was attributed to b/ops.py's unrelated import)."""
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    (tmp_path / "a" / "train.py").write_text(
        "def helper(x):\n    return float(x.mean())\n"
    )
    (tmp_path / "b" / "train.py").write_text("def helper(x):\n    return x\n")
    (tmp_path / "b" / "ops.py").write_text(
        textwrap.dedent(
            """
            import jax
            from train import helper

            @jax.jit
            def step(x):
                return helper(x)
            """
        )
    )
    res = run_analysis([str(tmp_path)], rules=get_rules(["host-sync-in-trace"]))
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_singleton_init_is_reachability_barrier(tmp_path):
    """Pin of the package triage: a borg-singleton __init__
    (`self.__dict__ = cls._shared_state`) runs once per process — traced
    code constructing the class must NOT drag the init body (host-side mesh
    building, np.asarray) into the traced region."""
    res = lint_pkg(
        tmp_path,
        {
            "state.py": """
                import numpy as np

                class State:
                    _shared_state = {}

                    def __init__(self):
                        self.__dict__ = self._shared_state
                        if not self.__dict__:
                            self.topo = np.asarray(enumerate_topology())
                """,
            "ops.py": """
                import jax
                from .state import State

                @jax.jit
                def step(x):
                    scale = State().topo
                    return x
                """,
        },
        rule="host-sync-in-trace",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_chained_attribute_call_does_not_link_same_name_method(tmp_path):
    """`self.state.update(x)` dispatches on an unknown receiver type — it
    must not create an edge to an unrelated same-module Metrics.update
    (review regression: depth-2 self chains linked by bare leaf name, so any
    common method name poisoned the traced region)."""
    res = lint(
        tmp_path,
        """
        import jax

        class Metrics:
            def update(self, v):
                self.total = float(v)       # host cast: fine, never traced

        class Trainer:
            @jax.jit
            def step(self, x):
                self.state.update(x)
                return x
        """,
        rule="host-sync-in-trace",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


# ---------------------------------------------------------------------------
# whole-program mode: cross-module donation + transitive-donation
# ---------------------------------------------------------------------------

def test_cross_module_donation_reuse(tmp_path):
    """A donating callable imported from another module (bare and through a
    module alias) participates in donation-reuse."""
    res = lint_pkg(
        tmp_path,
        {
            "opt.py": """
                import functools
                import jax

                @functools.partial(jax.jit, donate_argnums=(0,))
                def apply_update(state, grads):
                    return state
                """,
            "train.py": """
                from . import opt
                from .opt import apply_update

                def train(state, grads):
                    new = apply_update(state, grads)
                    return state + new          # read after donation

                def train_dotted(state, grads):
                    new = opt.apply_update(state, grads)
                    return state + new          # same, via module alias
                """,
        },
        rule="donation-reuse",
    )
    assert len(res.new_findings) == 2, [f.render() for f in res.new_findings]
    assert {f.symbol for f in res.new_findings} == {"train", "train_dotted"}


def test_transitive_donation_cross_module(tmp_path):
    """A helper in another module stores the buffer; donating it afterwards
    leaves the stored alias dangling — even though the local name was
    correctly rebound (which is why donation-reuse cannot see it)."""
    files = {
        "stash.py": """
            _HISTORY = []

            def remember(x):
                _HISTORY.append(x)

            def peek(x):
                return x.mean()
            """,
        "train.py": """
            import jax
            from .stash import remember, peek

            def f(a):
                return a * 2

            g = jax.jit(f, donate_argnums=(0,))

            def train(x):
                remember(x)
                x = g(x)
                return x
            """,
    }
    res = lint_pkg(tmp_path, files, rule="transitive-donation")
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    f = res.new_findings[0]
    assert "remember" in f.message and "stash.py" in f.message
    # donation-reuse stays silent (the local name WAS rebound)
    res = lint_pkg(tmp_path, files, rule="donation-reuse")
    assert res.new_findings == [], [f.render() for f in res.new_findings]
    # a helper that only reads is fine
    good = dict(files)
    good["train.py"] = files["train.py"].replace("remember(x)", "peek(x)")
    res = lint_pkg(tmp_path, good)
    assert res.new_findings == [], [f.render() for f in res.new_findings]


# ---------------------------------------------------------------------------
# whole-program mode: blocking through a helper in another module
# ---------------------------------------------------------------------------

def test_blocking_through_cross_module_helper(tmp_path):
    res = lint_pkg(
        tmp_path,
        {
            "syncs.py": """
                def hard_sync(x):
                    x.block_until_ready()
                    return x
                """,
            "loop.py": """
                from .syncs import hard_sync

                def train(step, batches):
                    for b in batches:
                        out = step(b)
                        hard_sync(out)
                    return out
                """,
        },
        rule="blocking-in-hot-loop",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    f = res.new_findings[0]
    assert f.path.endswith("loop.py") and "hard_sync" in f.message


def test_blocking_helper_with_internal_guard_is_clean(tmp_path):
    """A helper that only blocks under a profiling guard does not poison its
    callers — including when the guard sits inside a loop/try in the helper
    (review regression: the structural scan must honor guards at any depth)."""
    res = lint_pkg(
        tmp_path,
        {
            "syncs.py": """
                def maybe_sync(x, profile=False):
                    if profile:
                        x.block_until_ready()
                    return x

                def drain(xs, profiling=False):
                    for x in xs:
                        if profiling:
                            x.block_until_ready()
                    return xs

                def launcher(xs):
                    def inner(y):
                        y.block_until_ready()   # nested def: its own function
                    return [x for x in xs]
                """,
            "loop.py": """
                from .syncs import maybe_sync, drain, launcher

                def train(step, batches):
                    for b in batches:
                        out = step(b)
                        maybe_sync(out)
                        drain(out)
                        launcher(out)
                    return out
                """,
        },
        rule="blocking-in-hot-loop",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_blocking_closure_is_off_without_whole_program(tmp_path):
    """--no-cross-module is the escape hatch back to the historical linter:
    only DIRECT blocking calls fire, helper-transitive ones do not — even
    same-module ones."""
    src = {
        "loop.py": """
            def sync_all(x):
                x.block_until_ready()
                return x

            def train(step, batches):
                for b in batches:
                    out = step(b)
                    sync_all(out)
                return out
            """,
    }
    on = lint_pkg(tmp_path, src, rule="blocking-in-hot-loop")
    assert len(on.new_findings) == 1, [f.render() for f in on.new_findings]
    off = lint_pkg(tmp_path, src, rule="blocking-in-hot-loop", cross_module=False)
    assert off.new_findings == [], [f.render() for f in off.new_findings]


# ---------------------------------------------------------------------------
# recompile-hazard: capture-cache awareness (unbucketed loader batches)
# ---------------------------------------------------------------------------

CAPTURE_LOOP_BAD = """
from torch.utils.data import DataLoader

def train(accelerator, dataset, step_fn):
    step = accelerator.compile_step(step_fn)
    loader = DataLoader(dataset, batch_size=8)
    for batch in loader:
        step(batch)
"""

CAPTURE_LOOP_GOOD = """
from torch.utils.data import DataLoader
from accelerate_tpu.data_loader import PaddingCollate

def train(accelerator, dataset, step_fn):
    step = accelerator.compile_step(step_fn)
    loader = DataLoader(
        dataset, batch_size=8, collate_fn=PaddingCollate(pad_to_multiple_of=128)
    )
    for batch in loader:
        step(batch)

def train_fixed(accelerator, ids, step_fn, bs):
    # fixed-shape slices out of one array: shapes cannot vary per step
    step = accelerator.compile_step(step_fn)
    for start in range(0, 128, bs):
        step(ids[start : start + bs])
"""


def test_capture_cache_recompile_hazard_fires(tmp_path):
    res = lint(tmp_path, CAPTURE_LOOP_BAD, rule="recompile-hazard")
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert "CapturedStep" in res.new_findings[0].message


def test_capture_loop_enumerate_wrapped_loader_is_flagged(tmp_path):
    """`for i, batch in enumerate(loader)` is the same unbucketed loader
    underneath (review regression: wrappers hid the loader; its padded twin
    must stay clean through the wrapper too)."""
    src = """
    from torch.utils.data import DataLoader
    {extra_import}

    def train(accelerator, dataset, step_fn):
        step = accelerator.compile_step(step_fn)
        loader = DataLoader(dataset, batch_size=8{collate})
        for i, batch in enumerate(loader):
            step(batch)
    """
    res = lint(
        tmp_path,
        src.format(extra_import="", collate=""),
        rule="recompile-hazard",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    res = lint(
        tmp_path,
        src.format(
            extra_import="from accelerate_tpu.data_loader import PaddingCollate",
            collate=", collate_fn=PaddingCollate()",
        ),
        rule="recompile-hazard",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_capture_cache_recompile_hazard_good_twin(tmp_path):
    res = lint(tmp_path, CAPTURE_LOOP_GOOD, rule="recompile-hazard")
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_capture_loop_self_referential_assignment_terminates(tmp_path):
    """`loader = loader` must not send the assignment chase into infinite
    recursion (review regression)."""
    res = lint(
        tmp_path,
        """
        def train(accelerator, loader, step_fn):
            step = accelerator.compile_step(step_fn)
            loader = loader
            for batch in loader:
                step(batch)
        """,
        rule="recompile-hazard",
    )
    assert len(res.new_findings) == 1  # still loader-shaped, still flagged


def test_capture_loop_loader_resolves_in_enclosing_scope(tmp_path):
    """Another function's local `loader` must not shadow the loop's own
    padded binding (review regression: name resolution was module-wide,
    last-assignment-wins)."""
    res = lint(
        tmp_path,
        """
        from torch.utils.data import DataLoader
        from accelerate_tpu.data_loader import PaddingCollate

        def train(accelerator, dataset, step_fn):
            step = accelerator.compile_step(step_fn)
            loader = DataLoader(
                dataset, batch_size=8, collate_fn=PaddingCollate(pad_to_multiple_of=128)
            )
            for batch in loader:
                step(batch)

        def evaluate(dataset):
            loader = DataLoader(dataset, batch_size=1)
            return [len(b) for b in loader]
        """,
        rule="recompile-hazard",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_capture_loop_under_module_level_guard_reported_once(tmp_path):
    """A function nested under a top-level `if` is scanned once, as its own
    scope — the module-scope walk must not descend into it (review
    regression: the same loop produced duplicate findings)."""
    res = lint(
        tmp_path,
        """
        from torch.utils.data import DataLoader

        if True:
            def main(accelerator, dataset, step_fn):
                step = accelerator.compile_step(step_fn)
                loader = DataLoader(dataset, batch_size=8)
                for batch in loader:
                    step(batch)
        """,
        rule="recompile-hazard",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


def test_capture_loop_module_level_loader_still_resolves(tmp_path):
    """A name unbound in the loop's function falls back to the module-level
    binding — the unpadded global loader is still a hazard."""
    res = lint(
        tmp_path,
        """
        from torch.utils.data import DataLoader

        loader = DataLoader(dataset, batch_size=8)

        def train(accelerator, step_fn):
            step = accelerator.compile_step(step_fn)
            for batch in loader:
                step(batch)
        """,
        rule="recompile-hazard",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


def test_constructor_escape_positions_skip_self(tmp_path):
    """Escape positions of Cls.__init__ must align with the CALLER's args
    (self dropped): storing arg 0 means the caller's first argument escapes,
    not its second (review regression: off-by-one both directions)."""
    files = {
        "stash.py": """
            class Stash:
                def __init__(self, kept, ignored):
                    self._kept = kept
            """,
        "train.py": """
            import jax
            from .stash import Stash

            def f(a):
                return a * 2

            g = jax.jit(f, donate_argnums=(0,))

            def bad(a, b):
                s = Stash(a, b)
                a = g(a)            # donates the STORED buffer
                return a

            def fine(a, b):
                s = Stash(a, b)
                b = g(b)            # donates the unstored one
                return b
            """,
    }
    res = lint_pkg(tmp_path, files, rule="transitive-donation")
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].symbol == "bad"


def test_derived_scalar_store_is_not_an_escape(tmp_path):
    """A helper that stores x.shape[0] (a python int) does not store the
    BUFFER — donating x afterwards is safe (review regression: any RHS
    mentioning the param counted as a store)."""
    res = lint(
        tmp_path,
        """
        import jax

        _STATS = {}

        def record_size(x):
            _STATS["n"] = x.shape[0]

        g = jax.jit(lambda a: a, donate_argnums=(0,))

        def train(x):
            record_size(x)
            x = g(x)
            return x
        """,
        rule="transitive-donation",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_buffer_stored_inside_container_literal_still_escapes(tmp_path):
    """The bare-Name restriction must not lose `_CACHE[k] = (x, meta)` —
    a container literal holding the param stores the buffer itself."""
    res = lint(
        tmp_path,
        """
        import jax

        _CACHE = {}

        def remember(x, tag):
            _CACHE["latest"] = (x, tag)

        g = jax.jit(lambda a: a, donate_argnums=(0,))

        def train(x):
            remember(x, "step")
            x = g(x)
            return x
        """,
        rule="transitive-donation",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


def test_tuple_unpack_pairs_targets_to_values(tmp_path):
    """`local, STATE[k] = buf, cfg` stores only cfg — buf lands in a plain
    local and must not count as an escape (review regression: any storing
    slot marked every RHS name); swapping the slots flips the verdict."""
    src = """
    import jax

    _STATE = {{}}

    def helper(buf, cfg):
        {unpack}
        return buf

    g = jax.jit(lambda a: a, donate_argnums=(0,))

    def train(x, cfg):
        helper(x, cfg)
        x = g(x)
        return x
    """
    res = lint(
        tmp_path,
        src.format(unpack='local, _STATE["cfg"] = buf, cfg'),
        rule="transitive-donation",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]
    res = lint(
        tmp_path,
        src.format(unpack='_STATE["buf"], local = buf, cfg'),
        rule="transitive-donation",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


def test_augassign_accumulator_is_not_an_escape(tmp_path):
    """`_ACC["sum"] += x` stores old+x — a NEW array, not an alias of x
    (review regression); `_ACC["log"] += [x]` is list-extend and still
    keeps the alias."""
    src = """
    import jax

    _ACC = {{"sum": 0, "log": []}}

    def helper(x):
        {stmt}

    g = jax.jit(lambda a: a, donate_argnums=(0,))

    def train(x):
        helper(x)
        x = g(x)
        return x
    """
    res = lint(
        tmp_path, src.format(stmt='_ACC["sum"] += x'), rule="transitive-donation"
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]
    res = lint(
        tmp_path, src.format(stmt='_ACC["log"] += [x]'), rule="transitive-donation"
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


def test_plain_import_dotted_donor_participates(tmp_path):
    """`import pkg.opt; pkg.opt.apply_update(x, g)` is the same donor as the
    from-import spelling (review regression: the fact maps only bound
    two-part `alias.fn` names, so the fully-dotted call was invisible)."""
    res = lint_pkg(
        tmp_path,
        {
            "opt.py": """
                import functools
                import jax

                @functools.partial(jax.jit, donate_argnums=(0,))
                def apply_update(state, grads):
                    return state
                """,
            "train.py": """
                import pkg.opt

                def train(state, grads):
                    new = pkg.opt.apply_update(state, grads)
                    return state + new      # read after donation
                """,
        },
        rule="donation-reuse",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].symbol == "train"


def test_same_module_constructor_escape_detected(tmp_path):
    """Coverage must not depend on where the class lives: a same-module
    constructor that stores a buffer is the same escape as an imported one
    (review regression: _visible_callables skipped own classes)."""
    res = lint(
        tmp_path,
        """
        import jax

        class Stash:
            def __init__(self, kept, ignored):
                self._kept = kept

        g = jax.jit(lambda a: a, donate_argnums=(0,))

        def train(a, b):
            s = Stash(a, b)
            a = g(a)            # donates the STORED buffer
            return a
        """,
        rule="transitive-donation",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].symbol == "train"


def test_transitive_donation_annotated_rebind_still_fires(tmp_path):
    """`x: Array = g(x)` evaluates the value before rebinding — the scanner
    must check the donation before clearing the escaped state (review
    regression: AnnAssign's default target-first field order)."""
    res = lint(
        tmp_path,
        """
        import jax

        _H = []

        def remember(x):
            _H.append(x)

        g = jax.jit(lambda a: a, donate_argnums=(0,))

        def train(x):
            remember(x)
            x: jax.Array = g(x)
            return x
        """,
        rule="transitive-donation",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]


def test_blocking_chain_message_keeps_root_cause(tmp_path):
    """A depth-2 chain (loop → outer → mid → block) must still name the
    terminal blocking call in the finding (review regression)."""
    res = lint_pkg(
        tmp_path,
        {
            "a.py": """
                def leaf_sync(x):
                    x.block_until_ready()
                """,
            "b.py": """
                from .a import leaf_sync

                def mid(x):
                    leaf_sync(x)
                """,
            "loop.py": """
                from .b import mid

                def train(step, batches):
                    for b in batches:
                        mid(step(b))
                """,
        },
        rule="blocking-in-hot-loop",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert "block_until_ready" in res.new_findings[0].message


# ---------------------------------------------------------------------------
# on-disk analysis cache
# ---------------------------------------------------------------------------

def test_cache_second_run_hits_and_replays_findings(tmp_path):
    cache_dir = str(tmp_path / "cache")
    first = lint_pkg(tmp_path, CROSS_HOST_SYNC_BAD, cache_dir=cache_dir)
    assert first.cache_misses > 0 and first.cache_hits == 0
    assert len(first.new_findings) == 1
    second = lint_pkg(tmp_path, CROSS_HOST_SYNC_BAD, cache_dir=cache_dir)
    assert second.cache_misses == 0
    assert second.cache_hits == first.cache_misses
    assert [f.render() for f in second.new_findings] == [
        f.render() for f in first.new_findings
    ]


def test_cache_edit_invalidates_only_the_edited_file(tmp_path):
    cache_dir = str(tmp_path / "cache")
    root = write_pkg(tmp_path, CROSS_HOST_SYNC_GOOD)
    run_analysis([str(root)], cache_dir=cache_dir)
    # a comment-only edit: content hash changes, cross-module facts don't
    ops = root / "ops.py"
    ops.write_text(ops.read_text() + "\n# cache probe\n")
    res = run_analysis([str(root)], cache_dir=cache_dir)
    assert res.cache_misses == 1, (res.cache_hits, res.cache_misses)
    assert res.cache_hits == res.files_analyzed - 1


def test_cache_cross_module_edit_invalidates_dependents(tmp_path):
    """Editing helpers.py so its helper becomes host-syncing must re-analyze
    helpers.py (content) AND change its findings even though ops.py replays
    — the env hash carries the new cross-module reached set."""
    cache_dir = str(tmp_path / "cache")
    root = write_pkg(tmp_path, CROSS_HOST_SYNC_GOOD)
    clean = run_analysis([str(root)], cache_dir=cache_dir)
    assert clean.new_findings == []
    (root / "helpers.py").write_text(
        textwrap.dedent(CROSS_HOST_SYNC_BAD["helpers.py"])
    )
    res = run_analysis([str(root)], cache_dir=cache_dir)
    assert len(res.new_findings) == 1
    assert res.new_findings[0].path.endswith("helpers.py")


def test_cache_ignores_stale_or_foreign_entries(tmp_path):
    from accelerate_tpu.analysis.cache import AnalysisCache

    cache = AnalysisCache(str(tmp_path / "c"))
    cache.store("a.py", "hash1", {"summary": {}, "results": {}})
    assert cache.load("a.py", "hash1") is not None
    assert cache.load("a.py", "hash2") is None      # content drift
    assert cache.load("b.py", "hash1") is None      # different file


def test_cache_env_eviction_is_lru_not_fifo(tmp_path):
    """The steady-state env must survive churn from other env variants: a
    cache hit refreshes recency, so eviction drops the least-recently-USED
    variant (review regression: insertion-order FIFO evicted the busiest
    env first while dead ones survived)."""
    cache_dir = str(tmp_path / "cache")
    root = write_pkg(tmp_path, CROSS_HOST_SYNC_GOOD)
    steady = get_rules(["host-sync-in-trace"])
    run_analysis([str(root)], rules=steady, cache_dir=cache_dir)  # seed: miss
    churn = [
        ["recompile-hazard"],
        ["axis-name-mismatch"],
        ["donation-reuse"],
        ["dtype-widen"],
        ["blocking-in-hot-loop"],
        ["transitive-donation"],
        ["sharding-spec-drift"],
        ["recompile-hazard", "dtype-widen"],
    ]
    for variant in churn:  # 8 variants: enough to overflow the 8-entry cap
        hit = run_analysis([str(root)], rules=steady, cache_dir=cache_dir)
        assert hit.cache_misses == 0
        run_analysis([str(root)], rules=get_rules(variant), cache_dir=cache_dir)
    final = run_analysis([str(root)], rules=steady, cache_dir=cache_dir)
    assert final.cache_misses == 0, "steady env was evicted by churn variants"


def test_package_warm_cache_run_is_fast(tmp_path):
    """Whole-program + cache: the warm path replays every module summary and
    finding without parsing a single file."""
    cache_dir = str(tmp_path / "cache")
    cold = run_analysis(["accelerate_tpu"], cache_dir=cache_dir)
    assert cold.findings == [], [f.render() for f in cold.findings]
    warm = run_analysis(["accelerate_tpu"], cache_dir=cache_dir)
    assert warm.findings == []
    assert warm.cache_hits == warm.files_analyzed
    assert warm.cache_misses == 0
    assert warm.duration_s < cold.duration_s


# ---------------------------------------------------------------------------
# CLI: new flags + rule kinds
# ---------------------------------------------------------------------------

def test_cli_list_rules_shows_kind():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    assert "[reachability" in proc.stdout and "[syntactic" in proc.stdout
    for line in proc.stdout.splitlines():
        assert "[reachability" in line or "[syntactic" in line, line


def test_cli_no_cross_module_flag(tmp_path):
    root = write_pkg(tmp_path, CROSS_HOST_SYNC_BAD)
    proc = _run_cli(str(root))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    proc = _run_cli(str(root), "--no-cross-module")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "cross-module OFF" in proc.stdout


def test_cli_cache_flags(tmp_path):
    root = write_pkg(tmp_path, CROSS_HOST_SYNC_GOOD)
    cache_dir = str(tmp_path / "cache")
    proc = _run_cli(str(root), "--cache-dir", cache_dir)
    assert proc.returncode == 0 and "miss" in proc.stdout
    proc = _run_cli(str(root), "--cache-dir", cache_dir)
    assert "hit" in proc.stdout and "/0 miss" in proc.stdout
    proc = _run_cli(str(root), "--cache-dir", cache_dir, "--no-cache")
    assert proc.returncode == 0
    assert "hit" not in proc.stdout  # cache bypassed entirely


# ---------------------------------------------------------------------------
# per-branch cache namespace
# ---------------------------------------------------------------------------

def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True,
    )


def test_cache_namespace_is_per_git_branch(tmp_path, monkeypatch):
    """Two long-lived branches must not ping-pong-invalidate each other's
    entries: each branch gets its own subdirectory under cache_dir, keyed on
    `git rev-parse --abbrev-ref HEAD` (ROADMAP open item)."""
    from accelerate_tpu.analysis.cache import AnalysisCache, branch_namespace

    repo = tmp_path / "repo"
    repo.mkdir()
    _git(repo, "init", "-q")
    (repo / "f.txt").write_text("x")
    _git(repo, "add", ".")
    _git(repo, "commit", "-qm", "seed")
    _git(repo, "checkout", "-q", "-b", "feature/one")
    monkeypatch.chdir(repo)

    assert branch_namespace() == "feature_one"  # path-safe sanitization
    cache_dir = str(tmp_path / "cache")
    cache = AnalysisCache(cache_dir)
    cache.store("a.py", "h1", {"summary": {}, "results": {}})
    assert cache.load("a.py", "h1") is not None
    assert os.path.isdir(os.path.join(cache_dir, "feature_one"))

    # a second branch sees a cold namespace, not the first branch's entries
    _git(repo, "checkout", "-q", "-b", "feature/two")
    other = AnalysisCache(cache_dir)
    assert other.namespace == "feature_two"
    assert other.load("a.py", "h1") is None
    other.store("a.py", "h2", {"summary": {}, "results": {}})

    # switching back: the original entries are intact (no ping-pong)
    _git(repo, "checkout", "-q", "feature/one")
    again = AnalysisCache(cache_dir)
    assert again.load("a.py", "h1") is not None
    assert again.load("a.py", "h2") is None


def test_cache_namespace_follows_analyzed_tree_not_cwd(tmp_path, monkeypatch):
    """Out-of-tree `graftlint /path/to/checkout`: the namespace must come
    from the *target* checkout's branch, not whatever repo (or non-repo)
    the process happens to run from."""
    from accelerate_tpu.analysis.cache import AnalysisCache, branch_namespace

    repo = tmp_path / "target"
    repo.mkdir()
    _git(repo, "init", "-q")
    (repo / "f.txt").write_text("x")
    _git(repo, "add", ".")
    _git(repo, "commit", "-qm", "seed")
    _git(repo, "checkout", "-q", "-b", "target-branch")

    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    monkeypatch.chdir(elsewhere)
    assert branch_namespace() == "detached"  # CWD is no repo
    assert branch_namespace(str(repo)) == "target-branch"
    cache = AnalysisCache(str(tmp_path / "cache"), root=str(repo))
    assert cache.namespace == "target-branch"


def test_cache_namespace_detached_fallback(tmp_path, monkeypatch):
    from accelerate_tpu.analysis.cache import AnalysisCache, branch_namespace

    # outside any work tree
    outside = tmp_path / "plain"
    outside.mkdir()
    monkeypatch.chdir(outside)
    assert branch_namespace() == "detached"

    # detached HEAD inside a repo
    repo = tmp_path / "repo"
    repo.mkdir()
    _git(repo, "init", "-q")
    (repo / "f.txt").write_text("x")
    _git(repo, "add", ".")
    _git(repo, "commit", "-qm", "seed")
    _git(repo, "checkout", "-q", "--detach")
    monkeypatch.chdir(repo)
    assert branch_namespace() == "detached"
    cache = AnalysisCache(str(tmp_path / "cache"))
    assert cache.namespace == "detached"


def test_cache_second_run_still_hits_across_instances_same_branch(tmp_path):
    """run_analysis-level: the namespacing must not break warm reuse within
    one branch (the repo itself is the 'branch' here — both runs share it)."""
    cache_dir = str(tmp_path / "cache")
    first = lint_pkg(tmp_path, CROSS_HOST_SYNC_GOOD, cache_dir=cache_dir)
    assert first.cache_misses > 0
    second = lint_pkg(tmp_path, CROSS_HOST_SYNC_GOOD, cache_dir=cache_dir)
    assert second.cache_misses == 0 and second.cache_hits == first.cache_misses


# ---------------------------------------------------------------------------
# collective-divergence: the rank-divergence taint rule (v12)
# ---------------------------------------------------------------------------


def _taint_for(tmp_path, source, fn_name, known=None, self_prefix=None):
    """Build a FunctionTaint over one function of a one-file fixture."""
    import ast

    from accelerate_tpu.analysis.engine import ModuleInfo
    from accelerate_tpu.analysis.taint import FunctionTaint

    f = tmp_path / "snippet.py"
    f.write_text(textwrap.dedent(source))
    mod = ModuleInfo(str(f), "snippet.py", f.read_text())
    fn = next(
        n
        for n in ast.walk(mod.tree)
        if isinstance(n, ast.FunctionDef) and n.name == fn_name
    )
    return FunctionTaint(mod, fn, known=known or {}, self_prefix=self_prefix)


def test_taint_sources_seed_locals(tmp_path):
    ft = _taint_for(
        tmp_path,
        """
        import os
        import time

        def f(state):
            rank = state.process_index
            host = os.environ["LOCAL_RANK"]
            probe = os.path.exists("/tmp/flag")
            now = time.monotonic()
            clean = state.num_processes
        """,
        "f",
    )
    assert {"rank", "host", "probe", "now"} <= ft.tainted
    assert "clean" not in ft.tainted


def test_taint_propagates_through_assignment_chains(tmp_path):
    ft = _taint_for(
        tmp_path,
        """
        def f(state):
            rank = state.process_index
            doubled = rank * 2
            label = f"worker-{doubled}"
            other = state.num_processes + 1
        """,
        "f",
    )
    assert {"rank", "doubled", "label"} <= ft.tainted
    assert "other" not in ft.tainted


def test_taint_killed_by_symmetry_merge(tmp_path):
    ft = _taint_for(
        tmp_path,
        """
        from ops import gather_object

        def f(state):
            local = state.process_index
            merged = gather_object([local])
            depth = agree_max(merged)
        """,
        "f",
    )
    assert "local" in ft.tainted
    assert "merged" not in ft.tainted
    assert "depth" not in ft.tainted


def test_taint_joins_over_branches(tmp_path):
    """A name clean on one path and divergent on the other joins to
    divergent."""
    ft = _taint_for(
        tmp_path,
        """
        def f(state, fallback):
            if fallback:
                who = 0
            else:
                who = state.process_index
            return who
        """,
        "f",
    )
    assert "who" in ft.tainted
    assert ft.return_direct


def test_taint_implicit_flow_under_divergent_test(tmp_path):
    """An assignment under a rank-divergent test is itself divergent even
    when the assigned value is clean."""
    ft = _taint_for(
        tmp_path,
        """
        def f(state):
            mode = "idle"
            if state.is_main_process:
                mode = "lead"
            return mode
        """,
        "f",
    )
    assert "mode" in ft.tainted
    assert ft.return_direct


def test_taint_single_process_body_assignments_stay_clean(tmp_path):
    """Inside a single-process gate nothing can diverge a mesh: the branch
    is unreachable multi-process, so its assignments don't taint."""
    ft = _taint_for(
        tmp_path,
        """
        def f(state):
            mode = "idle"
            if state.num_processes == 1:
                mode = local_probe()
            return mode
        """,
        "f",
    )
    assert "mode" not in ft.tainted
    assert not ft.return_direct


def test_return_flow_digest(tmp_path):
    import ast

    from accelerate_tpu.analysis.engine import ModuleInfo
    from accelerate_tpu.analysis.taint import return_flow

    f = tmp_path / "snippet.py"
    f.write_text(
        textwrap.dedent(
            """
            def direct(state):
                return state.process_index

            def pending(state):
                return helper(state)

            def clean(state):
                return state.num_processes
            """
        )
    )
    mod = ModuleInfo(str(f), "snippet.py", f.read_text())
    fns = {
        n.name: n for n in ast.walk(mod.tree) if isinstance(n, ast.FunctionDef)
    }
    assert return_flow(mod, fns["direct"]) == (True, [])
    assert return_flow(mod, fns["pending"]) == (False, ["helper"])
    assert return_flow(mod, fns["clean"]) == (False, [])


def test_divergence_mismatched_counts_both_branches(tmp_path):
    """Both branches issue collectives, but different sequences — still a
    divergent schedule."""
    res = lint(
        tmp_path,
        """
        from ops import broadcast, gather_object

        def f(state, x):
            if state.process_index == 0:
                broadcast(x)
                broadcast(x)
            else:
                broadcast(x)
        """,
        rule="collective-divergence",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert "broadcast" in res.new_findings[0].message


def test_divergence_loop_over_fs_probe(tmp_path):
    """Polling a filesystem flag around a collective: hosts observe the flag
    at different times, so trip counts diverge."""
    res = lint(
        tmp_path,
        """
        import os

        def wait_for_go(state):
            while not os.path.exists("/tmp/go"):
                state.wait_for_everyone()
        """,
        rule="collective-divergence",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert "loop" in res.new_findings[0].message


def test_divergence_single_process_gate_exempts(tmp_path):
    """The sanctioned PR-13 autopilot shape: the divergent serving signal
    only drives a resize under a single-process world gate."""
    res = lint(
        tmp_path,
        """
        def _multi_process(state):
            return state.num_processes > 1

        def autoscale(state, fleet, telemetry):
            record = telemetry.serving_signal()
            if record and not _multi_process(state):
                fleet.resize(2)
        """,
        rule="collective-divergence",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_divergence_symmetric_guard_after_gather_is_clean(tmp_path):
    """The rank-symmetric rewrite of the serving-signal gate: gather first,
    agree on the merged view, then resize on every rank together."""
    res = lint(
        tmp_path,
        """
        from ops import gather_object

        def autoscale(state, fleet, telemetry):
            record = telemetry.serving_signal()
            depth = record.get("queue_depth", 0) if record else 0
            merged = gather_object([depth])
            if agree_max(merged) > 8:
                fleet.resize(2)
        """,
        rule="collective-divergence",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_divergence_cross_module_collective_helper(tmp_path):
    """The collective hides behind a helper in another module; the
    collective-closure alias map carries it to the divergent guard."""
    res = lint_pkg(
        tmp_path,
        {
            "sync.py": """
                def rendezvous(state):
                    state.wait_for_everyone()
                """,
            "train.py": """
                from .sync import rendezvous

                def run(state):
                    if state.is_main_process:
                        rendezvous(state)
                """,
        },
        rule="collective-divergence",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].symbol == "run"
    assert "rendezvous" in res.new_findings[0].message


def test_divergence_cross_module_needs_whole_program(tmp_path):
    """Same fixture with cross-module analysis off: the helper's collective
    is invisible, the rule stays silent (kind=reachability contract)."""
    res = lint_pkg(
        tmp_path,
        {
            "sync.py": """
                def rendezvous(state):
                    state.wait_for_everyone()
                """,
            "train.py": """
                from .sync import rendezvous

                def run(state):
                    if state.is_main_process:
                        rendezvous(state)
                """,
        },
        rule="collective-divergence",
        cross_module=False,
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_divergence_return_closure_crosses_modules(tmp_path):
    """A helper in another module RETURNS rank-divergent state; branching on
    its result over a collective fires at the caller."""
    res = lint_pkg(
        tmp_path,
        {
            "ident.py": """
                def whoami(state):
                    return state.process_index
                """,
            "train.py": """
                from .ident import whoami

                def run(state, fleet):
                    if whoami(state) == 0:
                        fleet.resize(2)
                """,
        },
        rule="collective-divergence",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert res.new_findings[0].symbol == "run"
    assert "whoami" in res.new_findings[0].message


def test_divergence_early_raise_before_collective(tmp_path):
    res = lint(
        tmp_path,
        """
        def run(state):
            if state.is_main_process:
                raise RuntimeError("lead only")
            state.wait_for_everyone()
        """,
        rule="collective-divergence",
    )
    assert len(res.new_findings) == 1, [f.render() for f in res.new_findings]
    assert "raise" in res.new_findings[0].message


def test_rank_local_watchdog_module_waives_divergence_scan(tmp_path):
    """telemetry/{flightrec,watchdog,trace_export}.py are rank-local by
    design (taint.RANK_LOCAL_MODULE_SUFFIXES): rank probes, per-rank dump
    files and divergent early exits ARE the point of a postmortem writer,
    so the divergence scan is waived for them."""
    res = lint_pkg(
        tmp_path,
        {
            "telemetry/watchdog.py": """
                import json

                def dump(state, events, path):
                    if state.process_index != 0:
                        path = f"{path}.rank{state.process_index}"
                    if not events:
                        return None
                    with open(path, "w") as f:
                        json.dump({"rank": state.process_index}, f)
                    return path
                """,
        },
        rule="collective-divergence",
    )
    assert res.new_findings == [], [f.render() for f in res.new_findings]


def test_rank_local_module_must_not_bear_a_collective(tmp_path):
    """The exemption's inverted contract: ANY collective in a rank-local-by-
    design module fires — even an unconditional one the divergence scan
    would never flag.  The postmortem path may run while the mesh is
    deadlocked; coordinating over the stalled mesh hangs the postmortem."""
    source = """
        def dump(state):
            state.wait_for_everyone()
            return state.process_index
        """
    exempt = lint_pkg(
        tmp_path / "exempt",
        {"telemetry/watchdog.py": source},
        rule="collective-divergence",
    )
    assert len(exempt.new_findings) == 1, [
        f.render() for f in exempt.new_findings
    ]
    assert "rank-local-by-design" in exempt.new_findings[0].message
    # the same unconditional collective is fine in an ordinary module: the
    # contract is inverted only where the divergence scan is waived
    plain = lint_pkg(
        tmp_path / "plain", {"sync.py": source}, rule="collective-divergence"
    )
    assert plain.new_findings == [], [f.render() for f in plain.new_findings]


def test_rank_local_suffix_list_pins_the_postmortem_modules():
    from accelerate_tpu.analysis.taint import rank_local_by_design

    assert rank_local_by_design("accelerate_tpu/telemetry/watchdog.py")
    assert rank_local_by_design("accelerate_tpu/telemetry/flightrec.py")
    assert rank_local_by_design("accelerate_tpu/telemetry/trace_export.py")
    assert rank_local_by_design("telemetry\\watchdog.py")  # windows seps
    # the exemption stays narrow: the rest of telemetry (and everything
    # else) keeps the full divergence scan
    assert not rank_local_by_design("accelerate_tpu/telemetry/__init__.py")
    assert not rank_local_by_design("accelerate_tpu/telemetry/metrics.py")
    assert not rank_local_by_design("accelerate_tpu/capture.py")


def test_package_suppressions_are_load_bearing():
    """The two in-tree suppressions (logging in_order overtaint, dispatcher
    handshake protocol) must each cover a finding the rule still detects:
    stripping the disable comment re-fires it.  Guards against the
    suppression rotting after the underlying code moves."""
    for rel in ("accelerate_tpu/logging.py", "accelerate_tpu/data_loader.py"):
        src = open(os.path.join(REPO, rel)).read()
        assert "graftlint: disable=collective-divergence" in src, rel
        with_suppression = run_analysis(
            [os.path.join(REPO, rel)], rules=get_rules(["collective-divergence"])
        )
        assert with_suppression.new_findings == [], rel
        assert with_suppression.suppressed >= 1, rel


def test_cli_sarif_output(tmp_path):
    bad, expected, _ = FIXTURES["collective-divergence"]
    (tmp_path / "bad.py").write_text(textwrap.dedent(bad))
    proc = _run_cli(str(tmp_path), "--format", "sarif")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    declared = {r["id"] for r in run["tool"]["driver"]["rules"]}
    results = run["results"]
    assert len(results) == expected
    for r in results:
        assert r["ruleId"] == "collective-divergence"
        assert r["ruleId"] in declared
        assert r["level"] == "error"
        assert "fix:" in r["message"]["text"]
        region = r["locations"][0]["physicalLocation"]["region"]
        assert region["startLine"] >= 1 and region["startColumn"] >= 1
        assert "graftlint/v1" in r["partialFingerprints"]
    # rule metadata carries the fix hint as SARIF help text
    by_id = {r["id"]: r for r in run["tool"]["driver"]["rules"]}
    assert by_id["collective-divergence"]["help"]["text"]


def test_cli_sarif_validates_under_sarif_check(tmp_path):
    """The exact pipeline `make lint-sarif` runs: graftlint --format sarif
    piped into tools/sarif_check.py."""
    bad, _, _ = FIXTURES["collective-divergence"]
    (tmp_path / "bad.py").write_text(textwrap.dedent(bad))
    proc = _run_cli(str(tmp_path), "--format", "sarif")
    check = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "sarif_check.py")],
        input=proc.stdout,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert check.returncode == 0, check.stdout + check.stderr


def test_cli_stale_baseline_fails(tmp_path):
    """A baseline matches exactly or fails: once the finding is fixed, the
    leftover entry must flunk the run until the baseline is regenerated."""
    bad, _, good = FIXTURES["collective-divergence"]
    f = tmp_path / "code.py"
    f.write_text(textwrap.dedent(bad))
    baseline = tmp_path / "baseline.json"
    assert _run_cli(str(tmp_path), "--write-baseline", str(baseline)).returncode == 0
    # baselined run is green while the finding exists
    assert _run_cli(str(tmp_path), "--baseline", str(baseline)).returncode == 0
    # the fix lands; the stale baseline entries must now fail the run
    f.write_text(textwrap.dedent(good))
    proc = _run_cli(str(tmp_path), "--baseline", str(baseline))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "stale" in proc.stdout
    data = json.loads(
        _run_cli(str(tmp_path), "--baseline", str(baseline), "--format", "json").stdout
    )
    assert data["baseline_stale"]
