"""Images trained a second over all the cell's chips: every image of the
window over the window's whole time (benchmarks/rates.whole_window_rate)."""


def read(run):
    return run.rate if run.traffic["unit"] == "img" else None
