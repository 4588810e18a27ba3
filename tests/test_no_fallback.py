"""A missing chip is an error, not a quieter path: the places that used to
hide the device (a CPU row under a per-chip name, the O(S²)
attention behind a failed import, interpreted kernels behind a swallowed
exception, a reshaped mesh behind a failed topology mapping, skip decisions
that opened a backend at import) fail where they used to fall back."""

import json
import os
import subprocess
import sys
import unittest

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def harness():
    """The yardstick's shared module, read and never edited (``benchmark/``
    puts the checkout's root on the path the way ``benchmark/run.py`` does)."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark import harness

    return harness


# ---------------------------------------------------------------------------
# benchmark/: what it prints is true of the device it names
# ---------------------------------------------------------------------------
def test_unknown_device_kind_is_an_error_not_a_default_peak(harness):
    flops = harness.flops
    assert flops.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["_source"]  # the table names where its peaks come from
    for kind in ("cpu", "TPU v9", "", "_source"):
        with pytest.raises(KeyError, match="not in benchmark/peaks.json"):
            flops.load_peaks(kind)


def test_the_benchmark_without_a_tpu_exits_3_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "gpt2-medium.train-1k", "--seed", "0", "--seconds", "1"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""  # no result line, under any name
    assert "the cell needs 1 tpu chip(s)" in proc.stderr


class _Device:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("platform, kind, count, why", [
    ("cpu", "cpu", 1, "needs 1 tpu chip"),  # another platform
    ("tpu", "TPU v5 lite", 4, "needs 1 tpu chip"),  # another count than the cell names
    ("tpu", "TPU v9", 1, "not in benchmark/peaks.json"),  # a chip without published peaks
])
def test_a_one_chip_cell_is_refused_on_any_other_machine(harness, monkeypatch, platform, kind, count, why):
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device(platform, kind)] * count)
    with pytest.raises(harness.NoChip, match=why):
        harness.require_chips(1)
    assert harness.open_cell("gpt2-medium.train-1k", "run.py") is None  # what run.py turns into exit 3


def test_the_chip_the_cell_names_is_reported_as_jax_reports_it(harness, monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device("tpu", "TPU v5 lite")])
    assert harness.require_chips(1) == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("numbers, correct", [
    ({"served_logit_gap": 0.03, "unfinished_requests": 0.0}, True),
    ({"served_logit_gap": 0.03, "unfinished_requests": 1.0}, False),  # one request never finished
    ({"served_logit_gap": 0.151, "unfinished_requests": 0.0}, False),  # a number over its limit
    ({"served_logit_gap": float("nan"), "unfinished_requests": 0.0}, False),
    ({"served_logit_gap": 0.03}, False),  # a limit without its number
    ({"served_logit_gap": 0.03, "unfinished_requests": 0.0, "unlisted": 0.0}, False),  # and the reverse
])
def test_a_failed_operation_or_a_number_over_its_limit_is_not_correct(harness, numbers, correct):
    with open(os.path.join(REPO, "benchmark", "limits", "gpt2-xl.serve-steady.json")) as f:
        limits = json.load(f)["limits"]
    ok, compared = harness.decide(numbers, limits)
    assert ok is correct
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = json.loads(harness.result_line(
        correct=ok, attempted=112, failed=int(numbers.get("unfinished_requests", 0)), metrics={},
        units={}, device=device, compared=compared,
    ))
    assert line["correct"] is correct and line["device"] == device
    assert line["compared"]["served_logit_gap"] == {
        "value": pytest.approx(numbers["served_logit_gap"], nan_ok=True), "limit": limits["served_logit_gap"],
    }


# ---------------------------------------------------------------------------
# attention / kernels / mesh
# ---------------------------------------------------------------------------
def test_flash_import_failure_on_a_tpu_backend_raises(monkeypatch):
    """On ``tpu`` a kernel that fails to import is an error; it does not
    warn once and run the O(S²) reference."""
    from accelerate_tpu.ops import attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setitem(sys.modules, "accelerate_tpu.ops.flash_attention", None)
    q = jnp.zeros((1, 2, 128, 64), jnp.bfloat16)
    with pytest.raises(ImportError):
        attention.sdpa_tpu(q, q, q, is_causal=True)
    # a shape the kernel cannot tile is the caller's to see, and runs the reference
    short = jnp.zeros((1, 2, 96, 64), jnp.bfloat16)
    assert attention.sdpa_tpu(short, short, short, is_causal=True).shape == short.shape


def test_kernel_policy_interpret_does_not_swallow(monkeypatch):
    """A backend that cannot be asked raises; it does not become "interpret"."""
    from accelerate_tpu.native.kernels import KernelPolicy

    def unreachable():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", unreachable)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        KernelPolicy(collective_matmul=True).interpret
    # and the interpreter is never forced onto a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="CPU verification only"):
        KernelPolicy(collective_matmul=True, interpret=True).interpret


def test_decode_attention_asks_its_backend_and_takes_no_switch(monkeypatch):
    """The decode program has one attention path and no argument that picks
    it or its lowering: the kernel asks what the flash kernels ask
    (``ops/flash_attention.py::_interpret``), which interprets exactly where
    the backend is not a TPU, and never on one."""
    import inspect

    from accelerate_tpu.native.kernels import paged_attention
    from accelerate_tpu.serving import engine

    for fn in (engine._decode_body, engine._decode_jit, engine._decode_n_jit,
               engine.run_decode, engine.run_decode_n, paged_attention.paged_attention):
        taken = set(inspect.signature(inspect.unwrap(fn)).parameters)
        assert not taken & {"paged", "kernel_interpret", "interpret", "kernels"}, fn
    # what the kernel's pallas_call is told, traced under either answer
    told = []
    real = paged_attention.pl.pallas_call
    monkeypatch.setattr(
        paged_attention.pl, "pallas_call",
        lambda *a, **kw: told.append(kw["interpret"]) or real(*a, **kw),
    )
    rows = jax.ShapeDtypeStruct((5, 8, 128), jnp.float32)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

    def trace():
        jax.eval_shape(
            lambda *a: paged_attention.paged_attention(*a, None, n_kv=2),
            jax.ShapeDtypeStruct((3, 4, 64), jnp.float32), rows, rows, ints(3, 2), ints(3), ints(),
        )

    trace()  # this backend is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trace()
    assert told == [True, False]


def test_mesh_keeps_the_plain_reshape_for_the_cpu_only(monkeypatch):
    from jax.experimental import mesh_utils

    from accelerate_tpu.parallel.mesh import make_mesh

    def refuse(*args, **kwargs):
        raise NotImplementedError("cannot map this topology")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    n = len(jax.devices())
    assert make_mesh({"dp": n}).shape["dp"] == n  # CPU: reshape, no topology asked

    class FakeTpu:
        platform = "tpu"

    with pytest.raises(NotImplementedError, match="cannot map this topology"):
        make_mesh({"dp": 4}, devices=[FakeTpu() for _ in range(4)])


def test_local_multiprocess_launch_on_an_accelerator_host_says_why(monkeypatch):
    from accelerate_tpu.commands.launch import launch_command_parser, multihost_launcher

    args = launch_command_parser().parse_args(["--num_processes", "2", "train.py"])
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(ValueError, match="ONE process drives all local chips"):
        multihost_launcher(args)


# ---------------------------------------------------------------------------
# importing a test file never initialises a backend
# ---------------------------------------------------------------------------
def test_require_decorators_decide_when_the_test_runs(monkeypatch):
    from accelerate_tpu.test_utils import testing

    asked = []

    def backend():
        asked.append(1)
        return "cpu"

    monkeypatch.setattr(testing, "_backend", backend)

    @testing.require_tpu
    def needs_tpu():
        return "ran"

    @testing.require_cpu
    def needs_cpu():
        return "ran"

    class Case(unittest.TestCase):
        def test_it(self):
            pass

    testing.require_non_cpu(Case)
    assert asked == []  # decorating asked nothing
    with pytest.raises(unittest.SkipTest, match="requires TPU"):
        needs_tpu()
    assert needs_cpu() == "ran"
    with pytest.raises(unittest.SkipTest, match="requires an accelerator"):
        Case("test_it").setUp()
    assert len(asked) == 3


def test_importing_the_test_harness_opens_no_backend():
    code = (
        "import accelerate_tpu.test_utils as t\n"
        "@t.require_tpu\n@t.require_multi_device\n@t.require_non_cpu\n"
        "def test_x(): pass\n"
        "from jax._src import xla_bridge\n"
        "print('INITIALIZED', xla_bridge.backends_are_initialized())\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "INITIALIZED False" in proc.stdout


def test_aot_store_loads_onto_the_meshs_devices_not_the_backends(compiled_in_this_process):
    """The jax 0.9 failure behind resize-prewarm: ``deserialize_and_load``
    defaults to every device of the backend, so a program compiled for a
    4-device sub-mesh came back expecting 8 shards."""
    from jax.experimental import serialize_executable
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from accelerate_tpu.native.aot_cache import _deserialize

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    half = jax.devices()[: len(jax.devices()) // 2]
    sharding = NamedSharding(Mesh(np.array(half), ("dp",)), P("dp"))
    x = jax.device_put(jnp.arange(4.0 * len(half)), sharding)
    compiled = jax.jit(lambda a: a * 2).lower(x).compile()
    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    entry = {"payload": payload, "in_tree": in_tree, "out_tree": out_tree}
    loaded = _deserialize(entry, half)
    np.testing.assert_array_equal(np.asarray(loaded(x)), np.asarray(x) * 2)


_IMPORT_THE_DECODE_KERNEL = """
import importlib
import sys

import jax
import jax.numpy as jnp

import accelerate_tpu.serving.engine  # what a serving process has before its first decode trace

assert "jax.experimental.pallas" not in sys.modules
answer = sys.argv[1]
jax.default_backend = lambda: answer
from accelerate_tpu.native.kernels import paged_attention as kernel

print("whole" if "jax._src.pallas.mosaic_gpu.core" in sys.modules else "without the GPU interpreter")
rows = jax.ShapeDtypeStruct((5, 8, 128), jnp.float32)
ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
out = jax.eval_shape(
    lambda *a: kernel.paged_attention(*a, None, n_kv=2),
    jax.ShapeDtypeStruct((3, 4, 64), jnp.float32), rows, rows, ints(3, 2), ints(3), ints(),
)
assert out.shape == (3, 4, 64)
importlib.import_module(kernel._GPU_INTERPRETER)  # the name is free again: who asks later gets it
"""


@pytest.mark.parametrize("answer, imported", [("tpu", "without the GPU interpreter"), ("cpu", "whole")])
def test_the_decode_kernels_pallas_import_follows_the_backend(answer, imported):
    """A serving process imports Pallas when its first decode program is
    traced, at every start.  On a TPU the kernel module leaves out jax's
    Mosaic-GPU interpreter (0.7 of the import's 1.0 s: nothing there can ask
    for it), the way jax itself allows for, and the kernel traces all the
    same.  Anywhere else the import is jax's own, whole.  A fresh interpreter
    each: the decision is made once, where Pallas is first imported."""
    proc = subprocess.run(
        [sys.executable, "-c", _IMPORT_THE_DECODE_KERNEL, answer],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == imported
