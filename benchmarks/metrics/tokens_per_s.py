"""Tokens trained a second over all the cell's chips: every token of the
window over the window's whole time (benchmarks/rates.whole_window_rate)."""


def read(run):
    return run.rate if run.traffic["unit"] == "tokens" else None
