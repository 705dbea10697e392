"""Models (``models/cca.py``, ``CCAMixer``), served: device milliseconds a
traced decode step spends under the layers' mixer paths (``cca``): the
projections into the latent, both convolutions over the carried tail, the
q-k mean and the value shift, a head's norm and rotary, the rows' write, the
attention over the slot's cached rows, the output projection.  From the
trace joined to the decode program's own names
(``benchmarks/serve_scopes.py``)."""

from benchmarks.metrics import kda_decode_ms

MODULE = "cca"


def read(run):
    return kda_decode_ms.per_call(run, MODULE)
