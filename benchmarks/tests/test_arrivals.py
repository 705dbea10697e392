"""The serving generator: a pure function of the traffic file."""

import json
import os

import numpy as np
import pytest

from benchmarks import arrivals
from benchmarks.run import load_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def mixes():
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    *_, below = load_cell(manifest, "dsc1p3b-code-0.8knee")
    # what a mix that ``extends`` it and sets ``rate`` and ``drain_s`` holds
    # (the rehearsal's dropped-in mix walks ``extends`` itself)
    above = dict(below, rate=below["rate"] * 1.3 / 0.8, drain_s=0)
    return below, above


def test_the_schedule_is_a_pure_function_of_the_file(mixes):
    below, _ = mixes
    a, b = arrivals.schedule(below, 35.0), arrivals.schedule(below, 35.0)
    for field in ("due_s", "prompt_len", "output_len"):
        assert np.array_equal(getattr(a, field), getattr(b, field))
    assert json.dumps(arrivals.describe(a)) == json.dumps(arrivals.describe(b))
    spec = below["arrivals"]
    assert a.prompt_len.min() >= spec["prompt_tokens"]["min"]
    assert a.prompt_len.max() <= spec["prompt_tokens"]["max"]
    assert a.output_len.min() >= spec["output_tokens"]["min"]
    assert a.output_len.max() <= spec["output_tokens"]["max"]
    assert np.all(np.diff(a.due_s) > 0) and a.due_s[-1] < 35.0
    # a longer run sends the same requests, and more
    longer = arrivals.schedule(below, 70.0)
    assert np.array_equal(longer.due_s[:len(a)], a.due_s)


def test_the_seed_sets_the_token_ids_and_never_the_work(mixes):
    below, _ = mixes
    sched = arrivals.schedule(below, 12.0)
    one = arrivals.prompts(below, sched, 1, 32256)
    big = arrivals.prompts(below, sched, 2**31 + 5, 32256)
    assert [len(p) for p in one] == [len(p) for p in big] \
        == list(sched.prompt_len)
    assert any(not np.array_equal(p, q) for p, q in zip(one, big))
    again = arrivals.prompts(below, sched, 1, 32256)
    assert all(np.array_equal(p, q) for p, q in zip(one, again))
    assert max(int(p.max()) for p in one) < 32256


def test_a_mix_that_sets_the_rate_sends_the_same_requests_closer(mixes):
    below, above = mixes
    assert above["arrivals"] == below["arrivals"]
    assert above["rate"] > below["rate"] and above["drain_s"] == 0
    n = 40
    slow = arrivals.schedule(below, 60.0)
    fast = arrivals.schedule(above, 60.0)
    assert np.array_equal(slow.prompt_len[:n], fast.prompt_len[:n])
    assert np.array_equal(slow.output_len[:n], fast.output_len[:n])
    assert np.allclose(slow.due_s[:n] * below["rate"],
                       fast.due_s[:n] * above["rate"])
    # a sweep's rate stands in for the file's
    swept = arrivals.schedule(below, 60.0, rate=above["rate"])
    assert np.array_equal(swept.due_s[:n], fast.due_s[:n])


def test_the_window_is_typical_of_the_long_run_it_is_rated_against(mixes):
    """The rate is set against the replica's long-run capacity, so the 35 s
    a run sends have to offer what the rate says: requests, prompt tokens
    and output tokens each within 5% of rate x 35 s x the long-run means
    (the rule ``schedule_seed`` was kept by; a file that changes ``rate``
    or the lengths has to look for its seed again)."""
    below, above = mixes
    horizon = below["lead_in_s"] + 30.0
    for share in arrivals.typical(below, horizon).values():
        assert abs(share - 1.0) <= 0.05
    mean = arrivals.long_run(below)
    spec = below["arrivals"]
    # the clipped log-normals' means, and the Poisson process's rate
    assert 1750 < mean["mean_prompt_tokens"] < 1900
    assert 25 < mean["mean_output_tokens"] < 28.5
    assert mean["rate_per_s"] == pytest.approx(below["rate"], rel=0.03)
    assert spec["prompt_tokens"]["median"] == 1500 \
        and spec["output_tokens"]["median"] == 13
    assert arrivals.long_run(above)["mean_output_tokens"] \
        == mean["mean_output_tokens"]


def test_an_unknown_kind_is_refused():
    with pytest.raises(ValueError, match="unknown arrivals kind"):
        arrivals.schedule({"rate": 1, "arrivals": {"kind": "nope"}}, 1.0)
