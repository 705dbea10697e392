"""Serving backend (``models/transformer.py``, ``cached_decode_attention``):
the least time the chip could take to read the keys and values the traced
decode steps needed -- each live slot's cached length times the bytes of K
and V a token holds over all layers (benchmarks/flops.py), over peak HBM
bandwidth -- over the device time the trace shows in the decode program's
operations under the layers' ``attn`` modules outside their four
projections (the cache update, QK', the softmax, PV, rope), in percent.
Bound by bytes: a decode step's attention does 2 FLOPs a byte."""

from benchmarks import flops


def read(run):
    if not hasattr(run, "records"):      # a training run: not this metric's
        return None
    t = run.trace
    if t is None or not t.decode_attn_s or run.peaks is None:
        return None
    steps = [e for e in run.traced_steps_log if e[0] == "decode"]
    if len(steps) != t.program_calls["decode"]:
        print(f"decode_attn_roofline: the loop logged {len(steps)} decode "
              f"calls while tracing and the trace holds "
              f"{t.program_calls['decode']}: not joined")
        return None
    least = flops.decode_attention_bytes(
        run.built.kv_bytes_per_token, [e[4] for e in steps]) \
        / run.peaks["hbm_bytes_per_s"]
    print(f"decode_attn_roofline: bound_by=bytes least_ms={1e3 * least:.3f} "
          f"took_ms={1e3 * t.decode_attn_s:.3f} decode_calls={len(steps)}")
    return 100.0 * least / t.decode_attn_s
