"""Models (``models/transformer.py``, ``EvaAttention``), served: device
milliseconds a traced decode step spends under the layers' ``attn`` paths:
the four projections and the rotation, the ring row's and the chunk
summary's writes, the summary itself, the attention over ring and
summaries.  From the trace joined to the decode program's own names
(``benchmarks/serve_scopes.py``)."""

from benchmarks import serve_scopes
from benchmarks.metrics.mla_decode_ms import seconds    # by module path


def read(run):
    s = seconds(run, "decode")
    if not s:
        return None
    return 1e3 * s / serve_scopes.of(run).calls["decode"]
