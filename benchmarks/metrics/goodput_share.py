"""Scheduler (``serving/engine.py``): share of the requests that fell due in
the window that met both limits of the traffic file, in percent: first
token within ``ttft_limit_ms`` of the due time, and a mean gap between its
tokens of at most ``tpot_limit_ms``.  A request rejected, or not finished
when the run ended, met neither."""


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    counted = run.counted
    if not counted:
        return None
    ttft, tpot = run.traffic["ttft_limit_ms"], run.traffic["tpot_limit_ms"]

    def met(r) -> bool:
        s = r.stamps
        if not r.done or not s:
            return False
        gap = 1e3 * (s[-1] - s[0]) / max(len(s) - 1, 1)
        return 1e3 * (s[0] - r.due) <= ttft and gap <= tpot

    return 100.0 * sum(map(met, counted)) / len(counted)
