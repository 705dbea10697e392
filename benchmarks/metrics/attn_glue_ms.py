"""Models (``models/transformer.py``): device milliseconds a step in XLA's
operations under a layer's ``attn`` module and under none of its four
projections -- rope, QK-norm, casts, and every copy that changes a layout
between a projection and a flash kernel.  No matmul and no kernel is in it
(the kernels are another kind; ``flash_ms``).  Read from the traced window
joined to the program's names (``scopes.Joined.module_s``); a program
that names nothing joins to nothing and the metric is left out."""

from benchmarks import scopes

PROJECTIONS = ("q", "k", "v", "o")


def is_glue(module: str) -> bool:
    """``Transformer/layer_N/attn`` and ``.../attn/q_norm``, not
    ``.../attn/q`` nor ``.../mlp/up``."""
    parts = module.split("/")
    if "attn" not in parts:
        return False
    inner = parts[parts.index("attn") + 1:]
    return not inner or inner[0] not in PROJECTIONS


def read(run):
    j = scopes.of(run)
    if j is None:
        return None
    seconds = sum(v for m, v in j.module_s.items() if is_glue(m))
    return 1e3 * seconds / run.traced_steps
