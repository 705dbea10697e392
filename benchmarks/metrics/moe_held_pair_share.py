"""Models (``models/moe.py``), served: of the (token, expert) pairs the
window's calls routed -- every position of a prompt and every slot of a
decode step that holds a request, each layer, ``num_experts_per_tok`` apiece
(a bucket's padding and an empty slot are routed nowhere) -- the share that
fell on experts this chip holds, in percent.  The program's own count
(the per-expert pairs each layer sows, which ``TransformerBackend`` hands
back a call).  Held experts over published ones where routing is even:
12.5% for 16 of 128."""


def read(run):
    if not hasattr(run, "records"):
        return None
    steps = [e for e in run.steps if run.inside(e[1]) and len(e) >= 6]
    if not steps:
        return None
    cfg = run.config
    per_token = cfg["num_hidden_layers"] * cfg["num_experts_per_tok"]
    # a prefill's prompt length, a decode step's live slots
    routed = sum((e[4] if e[0] == "prefill" else e[3]) * per_token
                 for e in steps)
    held = sum(sum(map(sum, e[5]["pairs"])) for e in steps)
    return 100.0 * held / routed
