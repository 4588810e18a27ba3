"""The trace reduction, on 60 ms of a real TPU v5e trace (GPT-2-medium train
step, PR 26) and on hand-made planes where the fixture has nothing to show."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "v5e_train_trace_60ms.json")
SPANS = ("loader.next", "step.dispatch", "step.sync")


@pytest.fixture(scope="module")
def planes():
    with open(FIXTURE) as f:
        return json.load(f)


def test_fixture_is_what_a_v5e_trace_looks_like(planes):
    names = [p["name"] for p in planes]
    assert names == ["/device:TPU:0", "/host:CPU"]
    assert {ln["name"] for ln in planes[0]["lines"]} == {tr.OP_LINE, tr.MODULE_LINE}


def test_busy_union_and_idle_share(planes):
    s = tr.summarize(planes, SPANS, "bench.window")
    assert s["window_s"] == pytest.approx(0.06)
    # ops overlap and abut; the union, not the sum, is busy time
    total = sum(d for _, _, d in tr.line_events(planes[0], tr.OP_LINE)) / 1e9
    assert s["busy_s"] == pytest.approx(0.05998378, abs=1e-9)
    assert s["busy_s"] <= s["window_s"] and s["busy_s"] <= total + 1e-12
    idle = 1 - s["busy_s"] / s["window_s"]
    assert 0 < idle < 0.001
    # the module line spans the whole step: had it counted, busy would be the window
    assert s["busy_s"] < s["window_s"]


def test_kernel_time_by_name(planes):
    fwd = tr.kernel_durations(planes, "flash_fwd")
    bwd = tr.kernel_durations(planes, "flash_bwd")
    assert (len(fwd), len(bwd)) == (3, 9)
    assert sum(fwd) == pytest.approx(0.001718458, abs=1e-9)
    assert sum(bwd) == pytest.approx(0.00848403, abs=1e-9)
    assert tr.kernel_durations(planes, "jit_traced", tr.MODULE_LINE)


def test_short_name_drops_operands():
    text = "%fusion.7 = bf16[8,1024]{1,0} fusion(%jvp_flash_fwd_.3, %p), kind=kLoop"
    assert tr.short_name(text) == "fusion.7"
    assert "flash_fwd" not in tr.short_name(text)


def test_idle_gaps_go_to_the_span_that_covers_them(planes):
    gaps = dict(tr.summarize(planes, SPANS, "bench.window")["breakdown"]["idle_gaps"])
    assert gaps["step.dispatch"] == pytest.approx(1.5872e-05, abs=1e-9)
    assert sum(gaps.values()) == pytest.approx(0.06 - 0.05998378, abs=1e-9)


def _plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v} for k, v in lines.items()]}


def test_exposed_collective_share_and_nested_spans():
    ms = 1_000_000
    chip = lambda ops: _plane("/device:TPU:0", **{tr.OP_LINE: ops})  # noqa: E731
    planes = [
        chip([
            ["fusion.1", 0, 10 * ms],
            ["all-gather-start.2", 5 * ms, 10 * ms],  # 5 ms hidden, 5 ms exposed
            ["fusion.3", 20 * ms, 5 * ms],
            ["all-reduce.4", 30 * ms, 2 * ms],  # wholly exposed
        ]),
        _plane("/host:CPU", python3=[
            ["bench.window", 0, 40 * ms],
            ["step.dispatch", 14 * ms, 10 * ms],
            ["loader.next", 16 * ms, 2 * ms],
        ]),
    ]
    assert tr.exposed_collective_seconds(planes, (0, 40 * ms)) == [pytest.approx(0.007)]
    assert tr.busy_seconds(planes, (0, 40 * ms)) == [pytest.approx(0.022)]
    gaps = dict(tr.idle_gaps_by_span(planes, ("step.dispatch", "loader.next"), (0, 40 * ms)))
    # gap 15-20 ms: loader.next (inner) takes 16-18, step.dispatch the rest
    assert gaps["loader.next"] == pytest.approx(0.002)
    assert gaps["step.dispatch"] == pytest.approx(0.003)
    assert gaps["(no span)"] == pytest.approx(0.005 + 0.008)
    with pytest.raises(ValueError):
        tr.traced_window([_plane("/device:TPU:0", **{tr.OP_LINE: []})])
