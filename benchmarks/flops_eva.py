"""Operations, bytes and rows of the ``eva_serve`` family's work, from
shapes alone: EVA attention over a prompt (a prefill: every query's exact set
E_t and summarised set R_t) and over a slot's ring and summaries (a decode
step), and what a slot's cache holds at a length.  Needed work only: what a
padded bucket, a masked tile, a stale ring row or a slot with no request
costs beyond it is not counted, so a share of a roofline computed from these
cannot pass 100% by over-counting.

With W = ``window_size`` and c = ``chunk_size`` (W a multiple of c), query t
of a sequence sees, exactly, the positions of its own window up to itself,
``t mod W + 1`` of them, and, summarised, one row a chunk of every window
wholly behind it, ``(W / c) floor(t / W)`` of them.
"""

from __future__ import annotations


def seen_rows(cfg: dict, t: int) -> tuple[int, int]:
    """(exact keys, summaries) the query at position ``t`` sees."""
    w, c = cfg["window_size"], cfg["chunk_size"]
    return t % w + 1, (w // c) * (t // w)


def prefill_pairs(cfg: dict, n: int) -> tuple[int, int]:
    """(query, exact key) and (query, summary) pairs of a prompt of ``n``
    positions, in closed form: full windows are triangles of W (W + 1) / 2,
    the last one of r = n mod W rows a smaller triangle; window w's queries
    see (W / c) w summaries each."""
    w, c = cfg["window_size"], cfg["chunk_size"]
    full, r = divmod(int(n), w)
    exact = full * (w * (w + 1) // 2) + r * (r + 1) // 2
    summarised = (w // c) * (w * (full * (full - 1) // 2) + r * full)
    return exact, summarised


def _per_pair(cfg: dict) -> float:
    """q . k and p . v of every head: 2 operations a unit of width each."""
    return 4.0 * cfg["hidden_size"]     # 4 * heads * head size


def prefill_attention_flops(cfg: dict, lengths) -> float:
    """The two products of every layer's attention over prompts of
    ``lengths`` positions, over E_t and R_t alike: 4 * heads * head size
    operations a (query, seen row) pair."""
    pairs = sum(sum(prefill_pairs(cfg, n)) for n in lengths)
    return _per_pair(cfg) * pairs * cfg["num_hidden_layers"]


def decode_attention_bytes(cfg: dict, steps_lengths, itemsize: int = 2
                           ) -> float:
    """HBM traffic the attention of decode steps cannot avoid: for each live
    slot the ring rows and summaries its query sees, K and V, once a layer.
    ``steps_lengths``: for each step, its live slots' lengths as the ENGINE
    counts them (the pending token included: the step's query sits at
    length - 1)."""
    rows = sum(sum(seen_rows(cfg, int(n) - 1))
               for lengths in steps_lengths for n in lengths)
    return float(2 * cfg["hidden_size"] * itemsize * rows
                 * cfg["num_hidden_layers"])


def held_rows(cfg: dict, n: int) -> int:
    """Rows of a slot's extent that hold something a later step may see,
    with ``n`` positions cached: the current window's ``n mod W`` ring rows
    and the ``n // c`` summaries of the chunks closed so far."""
    return int(n) % cfg["window_size"] + int(n) // cfg["chunk_size"]


def reserved_rows(cfg: dict, max_seq_len: int) -> int:
    """Rows a slot reserves: the ring and one summary a chunk."""
    return cfg["window_size"] + -(-int(max_seq_len) // cfg["chunk_size"])
