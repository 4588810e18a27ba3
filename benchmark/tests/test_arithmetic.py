"""flops.py against hand counts, the percentile arithmetic, the generator, the
admission of a metric by its runs' spread."""

import collections
import json
import math
import os

import numpy as np
import pytest

from benchmark import cells, flops, noise, stats, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,stored,millions", [
    ("gpt2-medium", 354_871_296, 354.9), ("gpt2-xl", 1_557_686_400, 1557.7),
])
def test_parameter_counts(name, stored, millions):
    cfg = config(name)
    e, n_layer = cfg["n_embd"], cfg["n_layer"]
    by_hand = (
        50304 * e + 1024 * e
        + n_layer * (2 * e + 2 * e + 3 * e * e + 3 * e + e * e + e + 4 * e * e + 4 * e + 4 * e * e + e)
        + 2 * e
    )
    assert flops.param_count(cfg) == by_hand == stored == cfg["parameters_stored"]
    assert round(stored / 1e6, 1) == millions
    assert flops.matmul_params(cfg) == n_layer * 12 * e * e + 50257 * e
    assert cfg["reduced"] == [] and cfg["n_embd"] // cfg["n_head"] == 64


def test_train_flops_per_token_medium():
    cfg = config("gpt2-medium")
    n = 24 * 12 * 1024 * 1024 + 50257 * 1024
    assert flops.train_flops_per_token(cfg, 1024) == 6 * n + 6 * 24 * 1024 * 1024
    assert flops.forward_flops_per_token(cfg) == 2 * n


def test_flash_costs_and_roofline():
    ops, nbytes = flops.flash_fwd_cost(8, 16, 1024, 64)
    assert ops == 2 * 8 * 16 * 1024 * 1024 * 64
    assert nbytes == 4 * 8 * 16 * 1024 * 64 * 2 + 8 * 16 * 1024 * 4
    ops_b, bytes_b = flops.flash_bwd_cost(8, 16, 1024, 64)
    assert ops_b == 2 * ops and bytes_b > nbytes
    peaks = flops.load_peaks("TPU v5 lite")
    assert (peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]) == (197e12, 819e9)
    least, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert bound == "compute" and least == pytest.approx(ops / 197e12)
    assert flops.roofline_seconds(1.0, 819e9, peaks) == (1.0, "memory")
    with pytest.raises(KeyError):
        flops.load_peaks("cpu")
    with pytest.raises(KeyError):
        flops.load_peaks("_source")


def test_decode_bytes():
    cfg = config("gpt2-xl")
    assert flops.kv_bytes_per_token(cfg) == 2 * 48 * 1600 * 2 == 307_200
    weights = (1_557_686_400 - 1024 * 1600) * 2
    assert flops.decode_step_bytes(cfg, 1000) == weights + 1000 * 307_200


def test_percentile_with_missing():
    values = list(range(1, 96))  # 95 finite
    assert stats.percentile_with_missing(values, 5, 95) == 95
    assert stats.percentile_with_missing(values, 6, 95) == math.inf  # rank 96 of 101 is missing
    assert stats.percentile_with_missing([3.0, 1.0, 2.0], 0, 50) == 2.0
    assert stats.percentile_with_missing([], 4, 95) == math.inf
    assert math.isnan(stats.percentile_with_missing([], 0, 95))
    assert stats.spread([10, 10.1, 10.2, 9.9, 9.8, 10]) == pytest.approx(0.025, abs=1e-9)


@pytest.mark.parametrize("pct", [50, 80, 95])
def test_ttft_percentile_readers(pct):
    read = cells.load_module("layer_metrics", f"ttft_p{pct}_ms.steady").read
    assert read({"counters": {"ttft_ms": list(range(99, 0, -1)), "ttft_missing": 1}}) == pct
    assert read({"counters": {"ttft_ms": [5.0], "ttft_missing": 99}}) == math.inf  # never served: above all
    assert read({"counters": {}}) is None  # a cell whose runner keeps no TTFTs: the metric is left out


@pytest.mark.parametrize("name", ["serve-steady"])
def test_serve_traffic_is_a_pure_function_of_the_seed(name):
    m = mix(name)
    a = traffic.serve_requests(m, 50257, 2**31 + 17, 20.0)
    b = traffic.serve_requests(m, 50257, 2**31 + 17, 20.0)
    c = traffic.serve_requests(m, 50257, 5, 20.0)
    assert len(a) == round(m["rate_per_s"] * 20.0)
    assert all(x.due_s == y.due_s and np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    assert [x.due_s for x in a] != [x.due_s for x in c]
    # every seed: the same sizes and gaps, in another order
    count = collections.Counter
    assert count(len(x.prompt) for x in a) == count(len(x.prompt) for x in c)
    assert count(x.max_new_tokens for x in a) == count(x.max_new_tokens for x in c)
    gaps = lambda r: sorted(np.diff([0.0] + [x.due_s for x in r]))  # noqa: E731
    assert gaps(a) == pytest.approx(gaps(c), abs=1e-8)
    assert all(0 < x.due_s < 20.0 for x in a)
    assert min(len(x.prompt) for x in a) >= m["prompt_len"]["low"]
    assert max(len(x.prompt) + x.max_new_tokens for x in a) <= m["service"]["max_request_len"]
    assert max(int(x.prompt.max()) for x in a) < 50257


def test_a_seed_orders_the_work_and_no_more():
    """A plain shuffle: gaps, prompt lengths and budgets are ordered apart, a
    seed can put long requests side by side, and only the stated distribution
    is known."""
    m = mix("serve-steady")
    runs = [traffic.serve_requests(m, 50257, seed, 51.0) for seed in (1, 2**31 + 7, 2**32 + 9)]
    assert len({round(r[-1].due_s, 9) for r in runs}) == 1  # the last request is due at the same time
    orders = [[x.max_new_tokens for x in r] for r in runs]
    assert orders[0] != orders[1] != orders[2]
    heaviest = [max(sum(o[i:i + 8]) for i in range(len(o) - 7)) for o in orders]
    assert len(set(heaviest)) > 1  # no dealing-out: the busiest stretch differs by seed
    with pytest.raises(ValueError):
        traffic.serve_requests(dict(m, output_len={"dist": "uniform", "low": 1, "high": 2}), 50257, 1, 5.0)


def test_train_rows_and_sample():
    m = mix("train-1k")
    rows = traffic.train_rows(m, 50257, 2**31 + 5)
    assert rows.shape == (m["distinct_batches"] * m["rows_per_step"], 1024)
    assert np.array_equal(rows, traffic.train_rows(m, 50257, 2**31 + 5))
    assert len({r.tobytes() for r in rows}) == len(rows) and rows.max() < 50257
    picked = traffic.sample_indices(40, 12, 9, always=7)
    assert picked[0] == 7 and len(set(picked)) == 12
    assert picked == traffic.sample_indices(40, 12, 9, always=7)


def around(share, far=None):
    """Six runs about 100 whose ``stats.spread`` is ``share`` (six runs put the
    quartiles a quarter of the way out from the 2nd and the 5th); ``far`` takes
    the place of the highest."""
    h = 100 * share / 2.5
    return [100 - 2 * h, 100 - h, 100 - h / 9, 100 + h / 9, 100 + h, far or 100 + 2 * h]


@pytest.mark.parametrize("shares,far,end_to_end", [
    ((0.04, 0.04), None, True),
    ((0.07,), None, True),  # the rule's own line
    ((0.04, 0.12), None, False),  # one set over it is enough
    ((0.12,), None, False),
    ((0.04,), 140.0, True),  # one run far off moves a quartile of six (0.132); the check leaves it out
])
def test_a_metric_is_admitted_by_the_spread_of_every_set(shares, far, end_to_end):
    sets = [around(share, far) for share in shares]
    if far is None:
        assert [stats.spread(s) for s in sets] == pytest.approx(list(shares))
    assert noise.admitted(sets) is end_to_end


def test_the_trimmed_range_leaves_out_the_farthest_run():
    assert stats.spread(around(0.04, far=140.0)) == pytest.approx(0.132)
    assert noise.trimmed_range(around(0.04)) == pytest.approx(0.048)  # 96.8 is as far as 103.2: one of them goes
    assert noise.trimmed_range(around(0.04, far=140.0)) == pytest.approx(0.048)
    assert noise.trimmed_range([10.0, 10.0, 10.0, 1.0]) == 0.0
    # the check's spread: without the farthest run where that narrows it, and never wider for it
    assert noise.check_spread(around(0.04, far=140.0)) == pytest.approx(stats.spread(around(0.04)[:-1]))
    assert noise.check_spread(around(0.04)) <= stats.spread(around(0.04))
    two_far = around(0.04, far=140.0)[1:] + [60.0]
    assert noise.check_spread(two_far) > 0.07 and not noise.admitted([two_far])  # two far-off runs do harm


@pytest.mark.parametrize("text,expected", [
    ('noise\n{"correct": true, "metrics": {"a_ms": {"value": 2.5, "unit": "ms"}}, "device": {}}\n', {"a_ms": 2.5}),
    ("[bench 10:00:00] window closed: 3/3 finished, drain 0.10s, {'serve_ttft_p80_ms': 81.25, 'x': inf}\n"
     "[bench 10:00:05] reference done: {'reference_s': 5.0}\n", {"serve_ttft_p80_ms": 81.25, "x": math.inf}),
    ("[bench 10:00:00] window closed: 294 steps in 51.4s\n", {}),
])
def test_noise_reads_a_result_line_or_the_serve_log(tmp_path, text, expected):
    path = tmp_path / "run.txt"
    path.write_text(text)
    assert noise.values_of(str(path)) == expected
