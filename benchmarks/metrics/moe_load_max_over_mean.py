"""Models (``models/moe.py``): how unevenly the router loaded the experts --
the fullest expert's (token, expert) pairs over the mean, from the counts the
first expert layer sows (``profiling.expert_load``), read by the family on
the pool's first batch after the window and kept in ``Built.notes``."""


def read(run):
    load = run.built.notes.get("expert_load")
    if load is None:
        return None
    print(f"moe_load: {load}")
    return load["max_over_mean"]
