"""Models (``models/moe.py``), served behind KDA and latent mixers:
``moe_held_pair_share`` counted from this family's keys: of the (token,
expert) pairs the window's calls routed -- every position of a prompt and
every slot of a decode step that holds a request, each SPARSE layer held,
``num_experts_per_tok`` apiece -- the share that fell on experts this chip
holds, in percent.  The program's own count.  25% for 128 of 512 were
routing even over the groups."""

from benchmarks import flops_kda


def read(run):
    if not hasattr(run, "records"):
        return None
    steps = [e for e in run.steps if run.inside(e[1]) and len(e) >= 6]
    if not steps:
        return None
    per_token = flops_kda.layers(run.config)["sparse"] \
        * run.config["num_experts_per_tok"]
    # a prefill's prompt length, a decode step's live slots
    routed = sum((e[4] if e[0] == "prefill" else e[3]) * per_token
                 for e in steps)
    held = sum(sum(map(sum, e[5]["pairs"])) for e in steps)
    return 100.0 * held / routed
