"""The one generator of serving traffic: a traffic file's ``arrivals`` group
in, an open-loop schedule of requests out (``streams.py`` is its like for
training batches).

The SCHEDULE -- when each request is due and how long its prompt and its
output are -- is a pure function of the traffic file: it is drawn from the
file's ``schedule_seed``, so every run of a cell, whatever its ``--seed``,
does the same work at the same times.  ``--seed`` sets the token ids (and the
weights) alone.  Request ``i`` has the same two lengths at every ``rate``:
the gaps between arrivals are drawn at rate 1 and divided by ``rate``, so a
mix that ``extends`` another and sets ``rate`` sends the same requests
closer together, and a sweep over rates compares like with like.

Kinds (a new mix is a new data file that names one of these):

``poisson_lognormal``  independent users: exponential gaps between arrivals
    (a Poisson process at ``rate`` requests a second); prompt and output
    lengths each log-normal, given by ``median`` and ``sigma`` and clipped
    to ``[min, max]`` (heavy-tailed, prompts much longer than answers).
    Every request emits exactly its output length: no EOS, greedy decoding.

Where the numbers come from is the traffic file's to say (its ``arrivals``
group's ``source``).  ``python3 -m benchmarks.arrivals <cell> <seconds>``
prints the long-run means of a mix beside what its first ``<seconds>`` hold,
for a range of ``schedule_seed``: a mix keeps the first seed whose window is
typical of its long run (:func:`typical`).
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np

from benchmarks import streams

# Draws made for every schedule, whatever its horizon: request i's lengths
# and unit-rate gap do not depend on how many requests a run reaches.
DRAWS = 8192


@dataclasses.dataclass(frozen=True)
class Schedule:
    due_s: np.ndarray           # seconds from the start of the arrival process
    prompt_len: np.ndarray      # tokens
    output_len: np.ndarray      # tokens to emit, exactly

    def __len__(self) -> int:
        return len(self.due_s)


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(np.log(float(spec["median"]))
               + float(spec["sigma"]) * rng.standard_normal(n))
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(
        np.int64)


def poisson_lognormal(spec: dict, rate: float) -> Schedule:
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(spec["schedule_seed"]), 1]))
    gaps = rng.exponential(1.0, DRAWS)
    prompt = _lognormal(rng, spec["prompt_tokens"], DRAWS)
    output = _lognormal(rng, spec["output_tokens"], DRAWS)
    return Schedule(np.cumsum(gaps) / float(rate), prompt, output)


KINDS = {"poisson_lognormal": poisson_lognormal}


def schedule(traffic: dict, horizon_s: float, rate: float | None = None
             ) -> Schedule:
    """Every request of the mix due before ``horizon_s``.  ``rate`` stands in
    for the file's own only in a sweep (``benchmarks/sweep.py``)."""
    spec = traffic["arrivals"]
    try:
        kind = KINDS[spec["kind"]]
    except KeyError:
        raise ValueError(f"unknown arrivals kind {spec.get('kind')!r}; "
                         f"benchmarks/arrivals.py has {sorted(KINDS)}") from None
    full = kind(spec, float(traffic["rate"] if rate is None else rate))
    n = int(np.searchsorted(full.due_s, horizon_s))
    if n >= DRAWS:
        raise ValueError(f"{horizon_s} s at this rate needs more than "
                         f"{DRAWS} requests: raise arrivals.DRAWS")
    return Schedule(full.due_s[:n], full.prompt_len[:n], full.output_len[:n])


def prompts(traffic: dict, sched: Schedule, seed: int, vocab: int
            ) -> list[np.ndarray]:
    """Request i's token ids: the first ``prompt_len[i]`` of row i of one
    pool of the training cells' stream, drawn from ``--seed``."""
    spec = dict(traffic["stream"], pool_batches=1)
    (rows,), = streams.make_pool(spec, seed, len(sched),
                                 seq_len=int(sched.prompt_len.max()),
                                 vocab=vocab)
    return [rows[i, :n] for i, n in enumerate(sched.prompt_len)]


def _quantiles(values) -> dict:
    v = sorted(float(x) for x in values)
    pick = lambda q: v[min(len(v) - 1, int(q * len(v)))]  # noqa: E731
    return {"min": v[0], "p50": statistics.median(v), "p95": pick(0.95),
            "max": v[-1]}


def describe(sched: Schedule) -> dict:
    """What was drawn, for the ``arrivals:`` line: it repeats to the digit
    in every run of a cell."""
    gaps = np.diff(sched.due_s, prepend=0.0)
    return {"requests": len(sched),
            "prompt_tokens": _quantiles(sched.prompt_len),
            "output_tokens": _quantiles(sched.output_len),
            "gap_ms": {k: round(1e3 * v, 3)
                       for k, v in _quantiles(gaps).items()},
            "sum_prompt_tokens": int(sched.prompt_len.sum()),
            "sum_output_tokens": int(sched.output_len.sum())}


def long_run(traffic: dict) -> dict:
    """The means of all ``DRAWS`` requests of the mix: what a rate set from
    the replica's capacity is set against."""
    full = KINDS[traffic["arrivals"]["kind"]](traffic["arrivals"],
                                              float(traffic["rate"]))
    return {"mean_prompt_tokens": float(full.prompt_len.mean()),
            "mean_output_tokens": float(full.output_len.mean()),
            "rate_per_s": DRAWS / float(full.due_s[-1])}


def typical(traffic: dict, horizon_s: float) -> dict:
    """The mix's first ``horizon_s`` seconds against its long run: requests,
    prompt tokens and output tokens sent, each over what ``rate`` and the
    long-run means would send in that time.  A window is typical where all
    three are near 1; one that is not offers another load than its ``rate``
    says."""
    sched, mean = schedule(traffic, horizon_s), long_run(traffic)
    expect = float(traffic["rate"]) * horizon_s
    return {"requests": len(sched) / expect,
            "prompt_tokens": float(sched.prompt_len.sum())
            / (expect * mean["mean_prompt_tokens"]),
            "output_tokens": float(sched.output_len.sum())
            / (expect * mean["mean_output_tokens"])}


if __name__ == "__main__":
    import copy
    import json
    import os
    import sys

    from benchmarks.run import load_cell

    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    *_, mix = load_cell(os.path.join(ROOT, "BENCHMARK.json"), sys.argv[1])
    for seed in range(2026, 2126):
        trial = copy.deepcopy(mix)
        trial["arrivals"]["schedule_seed"] = seed
        print(seed, json.dumps({k: round(v, 3) for k, v in typical(
            trial, float(sys.argv[2])).items()}), json.dumps(long_run(trial)))
