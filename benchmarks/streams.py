"""The one traffic generator: a traffic file's ``stream`` group in, a pool
of host batches out.  Everything is drawn from ``--seed``; a pool has the
same shapes for every seed, so the seed changes values and never the work.

Kinds (a new mix is a new data file that names one of these):

``markov_zipf_tokens``  token sequences with something to learn: a token is,
    with probability ``follow_prob``, the fixed successor of the token before
    it (a seeded permutation of the vocabulary), and otherwise a fresh draw
    from a Zipf law of exponent ``zipf_a`` over the vocabulary.  Runs of
    successors are cut at ``max_run`` so the whole pool is made in
    ``max_run`` vectorised passes.  Batch leaves: ``(tokens int32 [B, S],)``.
``class_pattern_images``  a fixed set of images with labels that can be
    learnt: unit normal noise plus ``pattern_gain`` times a seeded coarse
    pattern (``pattern_cells`` x ``pattern_cells`` x 3, upsampled) that
    belongs to the image's class.  Batch leaves: ``(images float32
    [B, H, W, 3], labels int32 [B])``.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, salt: int) -> np.random.Generator:
    # --seed may exceed 2**31; SeedSequence takes any non-negative integer.
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)), salt]))


def _zipf_draw(rng, vocab: int, a: float, shape) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -a)
    cdf /= cdf[-1]
    return np.searchsorted(cdf, rng.random(shape)).astype(np.int32)


def markov_zipf_tokens(spec: dict, seed: int, batch: int, *, seq_len: int,
                       vocab: int) -> list[tuple[np.ndarray]]:
    rng = _rng(seed, 1)
    n = int(spec["pool_batches"])
    max_run = int(spec["max_run"])
    succ = rng.permutation(vocab).astype(np.int32)
    shape = (n * batch, seq_len)
    tok = _zipf_draw(rng, vocab, float(spec["zipf_a"]), shape)
    follow = rng.random(shape) < float(spec["follow_prob"])
    follow[:, 0] = False
    # run[t]: successor steps from the last fresh draw to t, restarting
    # with a fresh draw after max_run of them.
    idx = np.arange(seq_len, dtype=np.int32)
    last_fresh = np.maximum.accumulate(np.where(follow, 0, idx), axis=1)
    run = (idx - last_fresh) % (max_run + 1)
    for t in range(1, max_run + 1):
        # a position with run t follows one with run t-1, already final
        before = np.concatenate([tok[:, :1], tok[:, :-1]], axis=1)
        tok = np.where(run == t, succ[before], tok)
    return [(tok[i * batch:(i + 1) * batch],) for i in range(n)]


def class_pattern_images(spec: dict, seed: int, batch: int, *, image: int,
                         classes: int) -> list[tuple[np.ndarray, np.ndarray]]:
    rng = _rng(seed, 2)
    n = int(spec["pool_batches"])
    cells = int(spec["pattern_cells"])
    if image % cells:
        raise ValueError(f"pattern_cells {cells} must divide image {image}")
    patterns = rng.standard_normal((classes, cells, cells, 3),
                                   dtype=np.float32)
    patterns *= np.float32(spec["pattern_gain"])
    up = image // cells
    pool = []
    for _ in range(n):
        labels = rng.integers(0, classes, batch, dtype=np.int32)
        x = rng.standard_normal((batch, image, image, 3), dtype=np.float32)
        x += np.repeat(np.repeat(patterns[labels], up, axis=1), up, axis=2)
        pool.append((x, labels))
    return pool


KINDS = {"markov_zipf_tokens": markov_zipf_tokens,
         "class_pattern_images": class_pattern_images}


def make_pool(spec: dict, seed: int, batch: int, **dims) -> list[tuple]:
    try:
        kind = KINDS[spec["kind"]]
    except KeyError:
        raise ValueError(f"unknown stream kind {spec.get('kind')!r}; "
                         f"benchmarks/streams.py has {sorted(KINDS)}") from None
    return kind(spec, seed, batch, **dims)
