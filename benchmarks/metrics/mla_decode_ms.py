"""Models (``models/transformer.py``, ``LatentAttention``), served: device
milliseconds a traced decode step spends under the layers' ``attn`` paths:
the down and up projections, the absorbed products, the attention over the
cached latents, the output projection.  From the trace joined to the decode
program's own names (``benchmarks/serve_scopes.py``)."""

from benchmarks import serve_scopes

MODULE = "attn"


def seconds(run, program: str, component: str = MODULE,
            kernel: str | None = None):
    """Device seconds of ``program`` under ``component``, or None."""
    j = serve_scopes.of(run)
    if j is None or not j.calls[program]:
        return None
    return j.under(program, component, kernel)


def read(run):
    s = seconds(run, "decode")
    if not s:
        return None
    return 1e3 * s / serve_scopes.of(run).calls["decode"]
