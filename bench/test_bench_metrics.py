"""The metric arithmetic on synthetic timestamps, and the traffic
generator's determinism."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import readers, workload

ROOT = Path(__file__).resolve().parent
B1 = json.loads((ROOT / "traffic" / "decode-b1.json").read_text())
BIG_SEED = 2**31 + 12345


def ctx(**kw):
    base = {"t0": 100.0, "seconds": 10.0, "emits": {}, "setup_s": 1.5}
    base.update(kw)
    return base


def test_percentiles_take_every_sample():
    # 1000 gaps of 1 ms and 60 of 50 ms: the p95 sits among the slow ones,
    # which a small reservoir or a coarse histogram would blur
    ts = np.cumsum(np.r_[0.0, np.full(1000, 1e-3), np.full(60, 50e-3)])
    c = ctx(emits={"a": ts.tolist()})
    gaps = np.diff(ts)
    assert readers.itl_p95_ms(c) == pytest.approx(
        1e3 * np.percentile(gaps, 95))
    assert readers.itl_p95_ms(c) == pytest.approx(50.0)


def test_token_gaps_stay_within_a_request():
    c = ctx(emits={"a": [100.0, 100.01, 100.02], "b": [105.0, 105.5]})
    assert sorted(readers.token_gaps_s(c)) == pytest.approx(
        [0.01, 0.01, 0.5])


def test_tokens_are_counted_inside_the_window_only():
    # window [100, 110): 99.9 is before it, 110.0 and later after it
    c = ctx(emits={"a": [99.9, 100.0, 105.0, 109.999],
                   "b": [109.5, 110.0, 111.0]})
    assert readers.tokens_in_window(c) == 4
    assert readers.output_tok_s(c) == pytest.approx(0.4)


def test_enqueue_per_step_and_setup():
    c = ctx(dispatch={"enqueue_s": 0.3, "steps": 1000})
    assert readers.enqueue_us(c) == pytest.approx(300.0)
    assert readers.setup_s(c) == 1.5
    assert readers.enqueue_us(ctx(dispatch={"enqueue_s": 0, "steps": 0})) \
        is None


def test_lengths_are_lognormal_quantiles_clipped():
    spec = {"median": 512, "sigma": 1.0, "min": 32, "max": 3072}
    x = workload.lengths(spec, 101)
    assert x.min() >= 32 and x.max() <= 3072
    assert np.median(x) == 512
    assert (np.diff(x) >= 0).all()


def test_closed_loop_seeds_shuffle_the_sizes():
    a = workload.closed_loop(B1, 151_936, 1)
    b = workload.closed_loop(B1, 151_936, 2)
    la = [next(a).max_new for _ in range(B1["pool"])]
    lb = [next(b).max_new for _ in range(B1["pool"])]
    assert sorted(la) == sorted(lb) and la != lb


def test_closed_loop_rounds_reuse_sizes_never_prompts():
    it = workload.closed_loop(B1, 151_936, BIG_SEED)
    reqs = [next(it) for _ in range(2 * B1["pool"])]
    first, second = reqs[:B1["pool"]], reqs[B1["pool"]:]
    assert sorted(q.max_new for q in first) == \
        sorted(q.max_new for q in second)
    assert len({q.prompt[:8].tobytes() for q in reqs}) == len(reqs)
    again = workload.closed_loop(B1, 151_936, BIG_SEED)
    assert all((next(again).prompt == q.prompt).all() for q in reqs[:5])


def test_every_request_fits_the_cache():
    workload.check(B1)
    worst = B1["prompt_len"]["max"] + B1["output_len"]["max"]
    assert worst <= B1["max_len"]
    with pytest.raises(ValueError):
        workload.check(dict(B1, max_len=worst - 1))
    with pytest.raises(ValueError):
        workload.check(dict(B1, loop="open"))


def test_sample_holds_the_longest_and_enough_tokens():
    reqs = [workload.Req(f"r{i}", np.zeros(10 + i, np.int32), 5)
            for i in range(20)]
    served = {q.rid: np.zeros(30, np.int32) for q in reqs}
    pick = workload.sample(reqs, served, BIG_SEED, 100)
    assert pick[0].rid == "r19"
    assert sum(len(served[q.rid]) for q in pick) >= 100
    assert pick == workload.sample(reqs, served, BIG_SEED, 100)
