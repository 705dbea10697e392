"""Models (``models/kda.py``, ``KDAMixer``), served: device milliseconds the
traced prefill programs spend under the KDA layers' mixer paths (``kda``:
projections, convolution, decay and step size, the chunked delta rule with
its state carried over the prompt's row blocks, norm and gate out), a
thousand prompt tokens admitted."""

from benchmarks.metrics import kda_decode_ms


def read(run):
    return kda_decode_ms.per_ktoken(run, kda_decode_ms.MODULE)
