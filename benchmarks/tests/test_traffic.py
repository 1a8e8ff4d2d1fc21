"""The one generator: reproducible from a seed, the same work for every seed,
lateness reported."""

import json
from pathlib import Path

import numpy as np

from benchmarks.harness import traffic as T

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic" / "chat-open-loop.json").read_text())


def test_same_seed_same_requests_and_large_seeds_work():
    a = T.open_loop_requests(MIX, 2**31 + 12345, 10.0, 200064)
    b = T.open_loop_requests(MIX, 2**31 + 12345, 10.0, 200064)
    assert a == b
    assert len(a["window"]) == round(MIX["arrivals"]["rate_per_s"] * 10.0)
    assert len(a["ramp"]) == round(MIX["arrivals"]["rate_per_s"] * MIX["ramp_s"])


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = T.open_loop_requests(MIX, 1, 20.0, 200064)["window"]
    b = T.open_loop_requests(MIX, 2, 20.0, 200064)["window"]
    assert sorted(len(r[1]) for r in a) == sorted(len(r[1]) for r in b)
    assert sorted(r[2] for r in a) == sorted(r[2] for r in b)
    assert [len(r[1]) for r in a] != [len(r[1]) for r in b]
    assert a[0][1] != b[0][1]  # other tokens
    lens = [len(r[1]) for r in a]
    assert min(lens) >= 32 and max(lens) <= 4096
    assert 400 < float(np.median(lens)) < 640  # log-normal around 512
    offs = [r[0] for r in a]
    assert offs == sorted(offs) and 0 <= offs[0] and offs[-1] < 20.0


def test_quantile_sizes_and_percentile():
    assert T.quantile_sizes({"dist": "uniform", "min": 0, "max": 100}, 4).tolist() == [12, 38, 62, 88]
    assert T.percentile([1.0, 2.0, 3.0, float("inf")], 0.95) == float("inf")
    assert T.percentile(list(range(1, 101)), 0.95) == 95


def test_lateness_is_reported():
    late = T.lateness([0.0, 1.0, 2.0], [0.1, 1.0, 2.5])
    assert late["n"] == 3 and late["max_s"] == 0.5


def test_arrivals_keep_bursts_and_sizes_are_not_evened_out():
    """The count is fixed; the gaps and the order of sizes are as free as an
    independent draw's: counts in 2 s bins disperse like a Poisson process's,
    and the output tokens of 16 consecutive requests swing as a free shuffle
    of this heavy tail does (dealt evenly into blocks of 16 they would not)."""
    disp, swing = [], []
    for seed in range(40):
        rng = np.random.default_rng(seed)
        t = T.arrival_offsets({"process": "exponential_gaps"}, 240, 50.0, rng)
        assert len(t) == 240 and 0 <= t[0] and t[-1] < 50.0 and (np.diff(t) > 0).all()
        counts, _ = np.histogram(t, bins=np.arange(0, 50.1, 2.0))
        disp.append(counts.var(ddof=1) / counts.mean())
        outs = np.asarray([r[2] for r in T.open_loop_requests(MIX, seed, 40.0, 1000)["window"]])
        blocks = [outs[i:i + 16].sum() for i in range(0, len(outs) - 15, 16)]
        swing.append(max(blocks) / min(blocks))
    assert 0.9 < float(np.mean(disp)) < 1.15
    assert float(np.median(swing)) > 1.5


def test_an_unknown_process_or_distribution_is_refused():
    import pytest

    with pytest.raises(ValueError):
        T.arrival_offsets({"process": "gamma", "cv": 2.5}, 10, 1.0, np.random.default_rng(0))
    with pytest.raises(ValueError):
        T.quantile_sizes({"dist": "fixed", "value": 3, "min": 1, "max": 9}, 4)
