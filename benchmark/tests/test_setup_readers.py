"""The readers of set-up and compilation (PR 42) on hand-made rings: the
outermost span alone counts, the set-up part ends where the window opens
(``span_readers.part(ctx)[0]``), a compile reads by its ``cache``, and a ring
that dropped events or a program without the compile listener reads ``None``."""

import pytest

from accelerate_tpu.telemetry import flightrec
from accelerate_tpu.telemetry.flightrec import FlightRecorder
from benchmark import cells, setup_readers

MS = 1_000_000
SETUP = ("setup_program_s", "setup_trace_s", "setup_compile_s", "setup_cache_load_s",
         "setup_programs_compiled")


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    monkeypatch.setattr(flightrec, "_RECORDER", FlightRecorder())


class FakeCell:
    def __init__(self, kind):
        self.mix = {"kind": kind}


def ring(window_compile=False):
    """Set-up then ten 10 ms engine steps, 1 s in the ring's past.

    Set-up: prepare (0-100 ms) and the service's construction (100-150), then
    one program traced (200-300, an inner trace and an eager compile nested in
    it), lowered (300-320) and compiled (320-420, a miss); a second program
    traced (430-440) and loaded from the cache (440-450, a hit); a third
    compiled with the cache off (460-470); a warm-up step (480-500).  The
    window opens at 1000 ms: its steps run 1000-1100, and with
    ``window_compile`` step 3 holds a trace and a compile of 4 ms."""
    rec = flightrec.recorder()
    t = rec.now_ns() - 2_000 * MS

    def span(name, a, b, **f):
        rec.record_span(name, t + a * MS, t + b * MS, **f)

    span("atpu/setup/prepare", 0, 100)
    span("atpu/serve/init", 100, 150)
    span("atpu/trace", 210, 220, fun="inner")
    span("atpu/compile", 230, 240, fun="jit(eager)", cache="miss")
    span("atpu/trace", 200, 300, fun="outer")
    span("atpu/lower", 300, 320, fun="jit(outer)")
    span("atpu/compile", 320, 420, fun="jit(outer)", cache="miss")
    span("atpu/trace", 430, 440, fun="second")
    span("atpu/compile", 440, 450, fun="jit(second)", cache="hit")
    span("atpu/compile", 460, 470, fun="jit(third)", cache="off")
    span("atpu/serve/step", 480, 500, step=0)
    for k in range(10):
        a = 1000 + 10 * k
        if window_compile and k == 3:
            span("atpu/trace", a + 1, a + 3, fun="late")
            span("atpu/compile", a + 3, a + 5, fun="jit(late)", cache="miss")
        span("atpu/serve/step", a, a + 10, step=k + 1)
    return {"cell": FakeCell("serve"), "counters": {"window_s": 0.1}, "planes": None, "summary": None}


def test_setup_counts_the_outermost_spans_before_the_window():
    got = setup_readers.setup(ring())
    assert got["trace_s"] == pytest.approx(0.100 + 0.020 + 0.010)  # outer, lower, second
    assert got["compile_s"] == pytest.approx(0.100 + 0.010)  # the miss and the cache off
    assert got["cache_load_s"] == pytest.approx(0.010)
    # every compile the cache did not serve counts, the nested eager one too
    assert got["programs_compiled"] == 3
    # prepare + init (0-150), the build (200-420), 430-450, 460-470, warm-up 480-500
    assert got["program_s"] == pytest.approx(0.150 + 0.220 + 0.020 + 0.010 + 0.020)
    assert got["trace_s"] + got["compile_s"] + got["cache_load_s"] <= got["program_s"]


def test_the_split_is_at_the_windows_open(capsys):
    ctx = ring(window_compile=True)
    assert setup_readers.setup(ctx)["programs_compiled"] == 3  # the window's compile is not set-up's
    assert setup_readers.compile_s_in_window(ctx) == pytest.approx(0.004)
    err = capsys.readouterr().err
    assert "in the window: atpu/trace late" in err and "inside step 4" in err
    assert "compiled in set-up: jit(third) cache=off" in err


def test_a_window_without_compiles_reads_zero():
    assert setup_readers.compile_s_in_window(ring()) == 0.0


def test_outermost_takes_a_span_whose_stamps_start_just_before_its_parents():
    a = {"start_ns": 1_000 * MS + 5_000, "end_ns": 1_100 * MS}
    b = {"start_ns": 1_000 * MS, "end_ns": 1_000 * MS + 50_000}  # nested, stamped 5 us early
    c = {"start_ns": 1_200 * MS, "end_ns": 1_300 * MS}
    assert setup_readers.outermost([b, a, c]) == [a, c]


def test_every_reader_gives_none_on_a_dropped_ring(monkeypatch, capsys):
    monkeypatch.setattr(flightrec, "_RECORDER", FlightRecorder(capacity=16))
    ctx = ring()
    assert setup_readers.setup(ctx) is None and setup_readers.compile_s_in_window(ctx) is None
    assert "dropped" in capsys.readouterr().err


def test_an_older_program_gives_none(monkeypatch):
    ctx = ring()
    monkeypatch.delattr(flightrec, "CompilePhases")
    assert setup_readers.setup(ctx) is None and setup_readers.compile_s_in_window(ctx) is None


def test_the_metrics_have_readers_and_entries():
    for name in SETUP:
        for cell in ("gpt2-medium.train-1k", "gpt2-xl.serve-steady"):
            assert name in cells.resolve(cell).per_layer
    serve, train = cells.resolve("olmo-hybrid-7b.serve-longout"), cells.resolve("gpt2-medium.train-1k")
    assert "compile_s_in_window.serve" in serve.per_layer and "compile_s_in_window.train" not in serve.per_layer
    assert "compile_s_in_window.train" in train.per_layer and "compile_s_in_window.serve" not in train.per_layer
    ctx = ring()
    for name in SETUP + ("compile_s_in_window.serve",):
        assert serve.layer_metric(name).read(ctx) is not None
