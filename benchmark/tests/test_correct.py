"""``correct`` comes out false when it should: the control (the reference in the
precision below the configuration's, put in the program's place) and each fault
a cell can have, planted under a whole run of the runner at a tiny size.  These
skip only the harness's look for a chip."""

import numpy as np
import pytest

import helpers
from benchmark import cells, compare, harness

SEED = 2**31 + 4242
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.fixture_repo(str(tmp_path_factory.mktemp("correct")))


@pytest.fixture(autouse=True)
def no_device_memory_stats(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 0)
    monkeypatch.setattr(harness, "memory_in_use_bytes", lambda: 0)


def drive(cell, **wrappers):
    import time

    out = cell.runner.run(cell, SEED, 0.5, False, time.perf_counter(), DEVICE, **wrappers)
    correct, compared = harness.decide(out["numbers"], cell.limits)
    return correct, compared, out


def test_sound_train_run_is_correct(root):
    correct, compared, out = drive(cells.resolve("tiny.train", root))
    assert correct, compared
    assert out["metrics"]["train_tokens_per_s"] > 0 and out["attempted"] == out["counters"]["steps"]


def test_train_control_is_not_correct(root):
    cell = cells.resolve("tiny.train", root)
    got = cell.runner.readings(cell, SEED, ["control", "half_batch"])
    assert harness.decide(dict(got["program"], nonfinite_window_losses=0.0), cell.limits)[0]
    for kind in ("control", "half_batch"):
        correct, compared = harness.decide(dict(got[kind], nonfinite_window_losses=0.0), cell.limits)
        assert not correct, (kind, compared)


def test_step_that_returns_its_state_unchanged(root):
    cell = cells.resolve("tiny.train", root)

    def frozen(step, prog):
        import jax.numpy as jnp

        first = {}

        def call(batch):  # the first call compiles and runs; later ones change nothing
            if not first:
                first["loss"] = step(batch)
                return first["loss"]
            return jnp.copy(first["loss"])

        return call

    correct, compared, _ = drive(cell, step_wrapper=frozen)
    assert not correct
    assert compared["update_norm_gap"]["value"] > 3 * compared["update_norm_gap"]["limit"]


def test_half_of_the_batch_left_out(root):
    cell = cells.resolve("tiny.train", root)

    def halved(step, prog):
        return lambda batch: step({k: v[: v.shape[0] // 2] for k, v in batch.items()})

    correct, compared, _ = drive(cell, step_wrapper=halved)
    assert not correct
    assert compared["grad_norm_gap"]["value"] > compared["grad_norm_gap"]["limit"]


def test_sound_serve_run_is_correct(root):
    correct, compared, out = drive(cells.resolve("tiny.serve", root))
    assert correct, compared
    assert out["failed"] == 0 and out["notes"]["tokens_compared"] > 20


def test_token_altered_where_it_is_produced(root):
    cell = cells.resolve("tiny.serve", root)

    def altering(step):
        def call():
            done = step()
            for req in done:
                req.tokens[len(req.tokens) // 2] = (req.tokens[len(req.tokens) // 2] + 1) % 1000
            return done

        return call

    correct, compared, _ = drive(cell, step_wrapper=altering)
    assert not correct
    assert compared["served_logit_gap"]["value"] > compared["served_logit_gap"]["limit"]


def test_serve_control_is_not_correct(root):
    cell = cells.resolve("tiny.serve", root)
    got = cell.runner.readings(cell, 11, ["control"], 0.8)
    assert got["program"]["served_logit_gap"] <= cell.limits["served_logit_gap"]
    assert got["control"]["served_logit_gap"] > cell.limits["served_logit_gap"]


def test_request_that_never_finishes_is_not_correct():
    numbers = compare.serve_numbers([np.zeros(3)], unfinished=1)
    assert not harness.decide(numbers, {"served_logit_gap": 0.1, "unfinished_requests": 0})[0]
    # a number without a limit, or a limit without its number, is not correct either
    assert not harness.decide({"a": 0.0}, {})[0] and not harness.decide({}, {"a": 1.0})[0]
