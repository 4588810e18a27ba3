"""chip_smoke.py's contract, as far as a machine without a chip can hold it
to it: the ``--rehearse-cpu`` control flow (one device, and four virtual
ones), the result line's schema, and every way the script must FAIL — no
TPU, a phase that raises, a directory that holds nothing else of the repo."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def run(args, cwd=REPO, env=None, script=SCRIPT, timeout=600):
    child_env = dict(os.environ if env is None else env)
    # the script sets its own device count; the suite's 8 must not leak in
    child_env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=child_env,
        capture_output=True, text=True, timeout=timeout,
    )


def phases(stdout: str) -> dict:
    """``{phase: [records]}`` of every line but the last."""
    out: dict = {}
    for line in stdout.strip().splitlines()[:-1]:
        if line.startswith("{"):
            record = json.loads(line)
            out.setdefault(record.get("phase"), []).append(record)
    return out


@pytest.fixture(scope="module")
def rehearsal():
    return run(["--rehearse-cpu"])


def test_rehearsal_control_flow_passes(rehearsal):
    assert rehearsal.returncode == 0, rehearsal.stderr[-3000:]
    seen = phases(rehearsal.stdout)
    assert list(seen) == ["start", "launch-worker", "launch", "train", "serve", "done"]
    (train,) = seen["train"]
    assert len(train["losses"]) >= 8 and train["losses"][-1] < train["losses"][0]
    assert train["recompiles_after_warmup"] == 0
    assert [leg["decode_steps"] for leg in seen["serve"]] == [1, 8]
    for leg in seen["serve"]:
        assert leg["requests"] >= 4 and leg["equal_to_generate"] == leg["requests"]
        assert leg["recompile_events"] == 0 and len(set(leg["prompt_lens"])) >= 4
        # one request fills its slot: at decode_steps 8 its last block is fed a
        # position past the slot's table
        assert leg["prompt_lens"][-1] + leg["new_tokens"][-1] == 128


def test_result_line_is_exactly_the_schema_and_names_the_cpu(rehearsal):
    last = rehearsal.stdout.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    # nothing more in that line, and it cannot be read as a chip pass
    assert last == '{"ok": true, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}'


def test_launched_worker_took_the_launchers_env_protocol(rehearsal):
    """The launch phase really went through ``accelerate-tpu launch``: the
    worker's mixed precision came from the launcher's environment."""
    (worker,) = phases(rehearsal.stdout)["launch-worker"]
    assert worker["mixed_precision"] == "bf16"
    assert worker["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert worker["first_step_s"] > 0


def test_without_a_tpu_it_fails_and_prints_no_result():
    for env in (dict(os.environ, JAX_PLATFORMS="cpu"), None):
        proc = run([], env=env)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
    for args in (["--chips", "4"],):
        proc = run(args)
        assert proc.returncode != 0 and '"ok"' not in proc.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    """The driver runs the script without the program: it must fail there,
    not pass on some fallback."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = run(["--rehearse-cpu"], cwd=str(tmp_path), env=env,
               script=str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "accelerate_tpu" in proc.stderr  # ModuleNotFoundError names it


def test_a_phase_that_raises_makes_the_exit_nonzero():
    """No phase is wrapped in a catch-all: the traceback reaches stderr, the
    exit code is non-zero and no result line is printed."""
    sabotage = textwrap.dedent(
        f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke

        def boom(*args, **kwargs):
            raise FloatingPointError("loss went to NaN at step 3")

        chip_smoke.launch_phase = lambda rehearse: None  # keep the test short
        chip_smoke.train_phase = boom
        sys.exit(chip_smoke.main(["--rehearse-cpu"]))
        """
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", sabotage], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "FloatingPointError: loss went to NaN at step 3" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_four_chip_rehearsal_on_four_virtual_devices():
    proc = run(["--rehearse-cpu", "--chips", "4"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    seen = phases(proc.stdout)
    # the sharded path and what it is compared with — and no one-chip phase
    assert list(seen) == ["start", "sharded", "sharded-compare", "sharded-serve", "serve", "done"]
    fsdp, dp = seen["sharded"]
    assert fsdp["mesh"] == {"fsdp": 4} and dp["mesh"] == {"dp": 4}
    assert fsdp["params_sharded"] > 0 and fsdp["opt_state_sharded"] > 0
    assert fsdp["collectives"]["all-gather"] > 0
    (compare,) = seen["sharded-compare"]
    for a, b in zip(compare["fsdp"], compare["dp"]):
        assert abs(a - b) <= compare["loss_rtol"] * abs(b)
    assert abs(compare["single_device_forward_step0"] - compare["fsdp"][0]) < 1e-3
    # the serve phase over weights sharded on all four: one decode program over
    # the mesh, the pools replicated on it, the kernel under shard_map
    (served,) = seen["sharded-serve"]
    assert served["mesh"] == {"fsdp": 4} and served["devices_a_parameter_spans"][-1] == 4
    assert [leg["decode_steps"] for leg in seen["serve"]] == [1, 8]
    for leg in seen["serve"]:
        assert leg["equal_to_generate"] == leg["requests"] >= 4 and leg["recompile_events"] == 0


@pytest.mark.parametrize(
    "logits, served, tie",
    [
        ([1.0, 2.3125, 2.296875, 0.5], 1, True),  # one bfloat16 step apart: either is some program's argmax
        ([1.0, 2.34375, 2.296875, 0.5], 1, False),  # three steps: a divergence
        ([2.3125, 2.3125, 2.296875, 0.5], 3, False),  # the served token is not one of the two
    ],
    ids=["one-step", "three-steps", "not-the-runner-up"],
)
def test_a_tie_is_told_from_a_divergence(logits, served, tie):
    """``first_divergence``: a served stream that leaves ``generate()`` where
    the two tokens are the forward's top 2, one step of the logits' dtype
    apart, left it at a tie (the decode kernel's logits agree to summation
    order, not bitwise); anything else is a divergence and fails the smoke."""
    import types

    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    import chip_smoke

    def model(ids):
        return {"logits": types.SimpleNamespace(data=jnp.asarray(logits, jnp.bfloat16)[None, None])}

    found = chip_smoke.first_divergence(model, [7, 7, served], [7, 7, 2], prompt_len=2)
    assert found["position"] == 2 and found["new_token_index"] == 0
    assert found["tie"] is tie


def test_launch_parent_never_initialises_a_backend(tmp_path):
    """A chip belongs to one process at a time: ``accelerate-tpu launch``
    starts its child from a process that has imported jax and opened no
    backend — so the child, not the launcher, gets the chip."""
    child = tmp_path / "child.py"
    child.write_text(
        "import jax\nprint('CHILD-DEVICES', len(jax.devices()), flush=True)\n"
    )
    probe = textwrap.dedent(
        f"""
        import sys
        sys.path.insert(0, {REPO!r})
        sys.argv = ["accelerate-tpu", "launch", "--mixed_precision", "bf16", {str(child)!r}]
        from accelerate_tpu.commands.accelerate_cli import main
        main()
        from jax._src import xla_bridge
        assert "jax" in sys.modules
        print("PARENT-BACKENDS-INITIALIZED", xla_bridge.backends_are_initialized())
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", probe], cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "CHILD-DEVICES" in proc.stdout
    assert "PARENT-BACKENDS-INITIALIZED False" in proc.stdout
