"""Family ``sdar_moe_serve``: an ``sdar_moe`` decoder (JetLM's
SDAR-30B-A3B-Chat: Qwen3-MoE's layer -- grouped-query attention with an
RMSNorm over each head of q and k, softmax-routed top-8 of 128 experts --
under a BLOCK-CAUSAL mask, generating by diffusion over blocks) served
through the path a user takes -- ``horovod_tpu.serving.ServingEngine`` over
``TransformerBackend`` in their block form: a prefill caches the prompt's
whole blocks and yields no token, a decode call is a PASS over every slot's
block of 4 positions that returns a token and a confidence a position, the
scheduler makes the positions its rule picks final and hands a request a
block when it is whole, and a block enters the cache whole with one more
pass, the commit -- weights and compute in bfloat16, the router in float32, greedy
picks with the mask's own id left out, no EOS.

The chip holds the FIRST OF EIGHT PIPELINE STAGES whole: every head, every
expert, the whole vocabulary (and the untied head, the last stage's in the
deployment, so that a token can be sampled).  Nothing walks: a prefill's
bucket and a pass's 48 x 4 positions go through the grouped products over
all 128 experts (``models/moe.py`` picks the form from the static shapes).
This family takes ``cohere2_moe_serve``'s drawing helpers and its judgement
of a token, and brings its own timing wrapper (a pass takes three
arguments) and its own comparison: every pass of a sample of blocks.

The configuration file holds Hugging Face's keys; this module maps them onto
``TransformerConfig`` and refuses what the program cannot express.  The
weights are the benchmark's own: drawn here from ``--seed``, a layer a
jitted call, in the type they are served in, handed to the program in its
layout and, drawn again after the window, to the plain reference in the
reference's.
"""

from __future__ import annotations

import functools
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.models.transformer import init_kv_cache
from horovod_tpu.serving import ServingConfig, ServingEngine
from horovod_tpu.serving.engine import TransformerBackend

from benchmarks import compare, scopes, serving
from benchmarks.families import cohere2_moe_serve as sparse
from benchmarks.reference import sdar_moe_serve as reference

seed_key, layer_key = sparse.seed_key, sparse.layer_key
F32, BF16 = jnp.float32, jnp.bfloat16

# The comparison's two numbers (ISSUE 56, tentpole 8), over every denoising
# pass of a sample of blocks of the requests the window finished; the
# reference is given the tokens before the block and the block's state going
# into the pass, as the timing wrapper saw the engine hand it to the backend
# (``TimedPasses``: what a pass made final is what the next state of its
# block no longer masks), and nothing else the program made:
#
# (a) served_token_gap_below_reference_best, as every served family has it:
#     how far the logit of each token the pass made final lies below the
#     reference's best non-mask token at its position, in that position's
#     standard deviations over the vocabulary.  Every block but a request's
#     first is computed against a cache that earlier commit passes wrote, so
#     a wrong or missing commit shows here.
# (b) chosen_position_confidence_gap: the reference's log-confidence at the
#     best masked positions of the state (as many as the pass made final)
#     less that at the positions the program chose, the MEAN over the judged
#     passes.  0 where the program chose the reference's own positions.  The
#     mean and not the widest: on drawn weights a block's positions differ
#     by about a tenth in log-confidence, a near-tie that bfloat16 decides
#     either way reads up to 0.08 at its widest (17 seeds) where a block
#     filled left to right, the rule's own control, reads 0.08-0.17 and the
#     float8 control 0.08-0.14: the widest gap tells none of them apart; a
#     sound pass is wrong only at near-ties and a wrong rule at every pass,
#     which the mean shows.  ``widest`` stands beside it on the check.
#
# The limits, fixed by PR 52's rule (between the largest sound reading over
# every seed run and the smallest control reading, nearer the sound ones),
# from the readings on the chip at the cell's own size (my chip runs, PR 56;
# CHANGES.md has every seed):
# (a) sound 0.011-0.117 over 50 seeds (227-240 tokens judged a reading, the
#     longest request 2955-4190 positions); the float8 control through this
#     same comparison 1.76-3.84 over 16 seeds, not correct on any.  0.4 is
#     3.4 times above the largest sound reading and 4.4 times below the
#     smallest control; their geometric middle is 0.45.
# (b) sound 0.0016-0.0040 over 33 seeds; its own control, a block filled left
#     to right, 0.0083-0.0165 over 32 of them; the float8 control 0.0093-
#     0.0143 over 12, not correct by this limit either on any.  0.006 is 1.5
#     times above the largest sound reading and 1.4 times below the smallest
#     control (their geometric middle is 0.0058): the room is small in ratio
#     and wide in spread, the sound readings' being 0.0006.
GAP_LIMIT = 0.4
CONFIDENCE_GAP_LIMIT = 0.006

def generation(cfg: dict, traffic: dict) -> dict:
    """How the model generates: the configuration's ``generation`` group
    (the released chat models' defaults, ``assumed``) with what the traffic
    mix sets of it (the rule and the steps a cell runs)."""
    return {**cfg["generation"], **traffic.get("generation", {})}


def model_config(cfg: dict, traffic: dict) -> TransformerConfig:
    refused = {
        "model_type": "sdar_moe", "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "sliding_window": None, "use_sliding_window": False,
        "rope_scaling": None, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "norm_topk_prob": True}
    wrong = {k: cfg.get(k) for k, v in refused.items() if cfg.get(k) != v}
    if wrong:
        raise ValueError(f"sdar_moe_serve builds {refused}; the "
                         f"configuration says {wrong}")
    gen = generation(cfg, traffic)
    # (a checkout before PR 56 has no such fields and says so at once:
    # TransformerConfig.from_dict names the first it does not know)
    return TransformerConfig.from_dict(dict(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        embed_dim=cfg["hidden_size"], mlp_dim=cfg["moe_intermediate_size"],
        qk_norm="head", attention_block=int(gen["block_length"]),
        mask_token_id=int(gen["mask_token_id"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), moe_selection="softmax",
        tie_embeddings=False, max_seq_len=int(traffic["max_seq_len"]),
        dtype="bfloat16", param_dtype="bfloat16"))


def serving_config(cfg: dict, traffic: dict) -> ServingConfig:
    gen = generation(cfg, traffic)
    return ServingConfig(
        num_slots=int(traffic["num_slots"]),
        buckets=tuple(int(b) for b in traffic["prefill_buckets"]),
        max_seq_len=int(traffic["max_seq_len"]), eos_id=None,
        denoise_steps=int(gen["denoising_steps"]),
        unmask_rule=gen["remasking"],
        confidence_threshold=float(gen["confidence_threshold"]))


def draw_layer(cfg: dict, key) -> dict:
    """One layer's weights in the reference's layout, bfloat16: normal with
    the ``assumed`` initializer_range (the router's logits then spread
    about 0.9 at a hidden size of 2048, so that no few ids decide the
    picks), every norm's scale at 1."""
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    n = cfg["num_experts"]
    normal = sparse._normal(float(cfg["initializer_range"]))
    ones = lambda m: jnp.ones((m,), BF16)  # noqa: E731
    k = iter(jax.random.split(key, 8))
    return {"input_layernorm": ones(e), "post_attention_layernorm": ones(e),
            "q_proj": normal(next(k), e, h * d),
            "k_proj": normal(next(k), e, kv * d),
            "v_proj": normal(next(k), e, kv * d),
            "o_proj": normal(next(k), h * d, e),
            "q_norm": ones(d), "k_norm": ones(d),
            "router": normal(next(k), e, n),
            "experts": {"gate_proj": normal(next(k), n, e, f),
                        "up_proj": normal(next(k), n, e, f),
                        "down_proj": normal(next(k), n, f, e)}}


def layer_to_program(w: dict, cfg: dict) -> dict:
    """One layer as ``models/transformer.py`` lays it out: reshapes and
    names alone."""
    e = cfg["hidden_size"]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    ex = w["experts"]
    return {"attn_norm": {"scale": w["input_layernorm"]},
            "mlp_norm": {"scale": w["post_attention_layernorm"]},
            "attn": {"q": {"kernel": w["q_proj"].reshape(e, h, d)},
                     "k": {"kernel": w["k_proj"].reshape(e, kv, d)},
                     "v": {"kernel": w["v_proj"].reshape(e, kv, d)},
                     "o": {"kernel": w["o_proj"].reshape(h, d, e)},
                     "q_norm": {"scale": w["q_norm"]},
                     "k_norm": {"scale": w["k_norm"]}},
            "moe_mlp": {"router": w["router"], "gate": ex["gate_proj"],
                        "up": ex["up_proj"], "down": ex["down_proj"]}}


def _ends(cfg: dict, key) -> dict:
    """The embedding, the final norm's scale and the untied head."""
    normal = sparse._normal(float(cfg["initializer_range"]))
    v, e = cfg["vocab_size"], cfg["hidden_size"]
    return {"embed_tokens": jax.jit(lambda k: normal(k, v, e))(
                jax.random.fold_in(key, 0)),
            "norm": jnp.ones((e,), BF16),
            "lm_head": jax.jit(lambda k: normal(k, e, v))(
                jax.random.fold_in(key, 1 << 20))}


@functools.lru_cache(maxsize=None)
def _layer_drawer(cfg_json: str, program: bool):
    cfg = json.loads(cfg_json)
    lay = (lambda w: layer_to_program(w, cfg)) if program else (lambda w: w)
    return jax.jit(lambda k: lay(draw_layer(cfg, k)))


def _numbers(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, list, bool, type(None)))}


def drawn_layer(cfg: dict, key, local: int, program: bool = False) -> dict:
    """Layer ``local``'s weights of the seed ``key``: one jitted call, so
    that no layer lies on the chip in two layouts at once."""
    return _layer_drawer(json.dumps(_numbers(cfg), sort_keys=True),
                         program)(layer_key(key, local))


def draw(cfg: dict, key) -> dict:
    """The weights in the reference's layout."""
    return {**_ends(cfg, key),
            "layers": [drawn_layer(cfg, key, i)
                       for i in range(cfg["num_hidden_layers"])]}


def to_program(w: dict, cfg: dict) -> dict:
    return {"params": {
        "embed": {"embedding": w["embed_tokens"]},
        "final_norm": {"scale": w["norm"]},
        "lm_head": {"kernel": w["lm_head"]},
        **{f"layer_{i}": layer_to_program(layer, cfg)
           for i, layer in enumerate(w["layers"])}}}


def program_params(cfg: dict, key) -> dict:
    """The seed's weights in the program's layout."""
    ends = _ends(cfg, key)
    return {"params": {
        "embed": {"embedding": ends["embed_tokens"]},
        "final_norm": {"scale": ends["norm"]},
        "lm_head": {"kernel": ends["lm_head"]},
        **{f"layer_{i}": drawn_layer(cfg, key, i, program=True)
           for i in range(cfg["num_hidden_layers"])}}}


class TimedPasses(sparse.TimedSparse):
    """``cohere2_moe_serve.TimedSparse`` for a block model's backend: a pass
    takes the blocks, their first positions and which slots are live, and
    is logged as every decode call is, ``("decode", start, end, live slots,
    live positions, {...})``: the positions a pass has to read are each
    live slot's cached ones and its block's; the sixth field is
    ``TimedSparse``'s (the pairs each expert was given, the live slots'
    cached lengths).

    It also keeps what the comparison judges and the engine's ``Request``
    does not: the ORDER in which positions became final.  Every pass's
    blocks going in are kept (``passes``; one append a pass: nothing a slot
    is done while the chip waits), a request's are cut out of them when the
    engine says it finished (:meth:`finished`, its ``on_complete``), and
    what a pass made final is what the next state of the same block no
    longer masks (:func:`passes_of`)."""

    def __init__(self, backend, threshold: float):
        super().__init__(backend)
        self.threshold = threshold
        # a pass: (the blocks' first positions, their ids going in, which
        # slots were live, the confidences that came back)
        self.passes: list[tuple] = []
        self.begun: dict[int, int] = {}     # slot -> its request's first pass
        self.by_request: dict[tuple, list] = {}

    def prefill(self, padded, length, slot):
        self.begun[int(slot)] = len(self.passes)
        return super().prefill(padded, length, slot)

    def decode(self, tok_block, lengths, live):
        going_in, first = tok_block.copy(), lengths.copy()  # (the engine's)
        t = serving.clock()
        with serving._annotate("decode"):
            out = self.inner.decode(tok_block, lengths, live)
        cached = lengths[live]
        self.log.append((
            "decode", t, serving.clock(), int(live.sum()),
            int(cached.sum()) + int(live.sum()) * tok_block.shape[1],
            {"pairs": self.inner.last_expert_pairs.tolist(),
             "lengths": cached.tolist()}))
        self.passes.append((first, going_in, live, out[2]))
        return out

    def finished(self, req) -> None:
        """The engine's ``on_complete``: the states the request's slot was
        given since its prefill, ``[(the block's first position, its ids),
        ...]``, by the request's own ids (``measure`` and ``control.py``
        hand the comparison the prompt and the tokens alone)."""
        s = req.slot
        self.by_request[_ids(req.prompt, req.tokens)] = [
            (int(first[s]), going_in[s])
            for first, going_in, _, _ in self.passes[self.begun[s]:]]

    @property
    def above_threshold(self) -> int:
        """Masked positions of live slots whose confidence passed the
        threshold: what the dynamic rule would have made final more."""
        return sum(int(((going_in == self.inner.mask_id)
                        & (conf > self.threshold))[live].sum())
                   for _, going_in, live, conf in self.passes)

    def forget(self) -> None:
        """Nothing kept of the passes so far (the warm-up's)."""
        del self.passes[:]
        self.by_request.clear()


def _ids(prompt, tokens) -> tuple:
    return (np.asarray(prompt, np.int32).tobytes(),
            np.asarray(tokens, np.int32).tobytes())


def passes_of(states: list, mask_id: int) -> list:
    """A request's denoising passes ``[(the block's first position, its ids
    going in, ((offset, token), ...) made final), ...]`` from the states its
    slot's passes were given: a pass over a block with masks made final
    what the next state of that block, the following pass's, no longer
    masks.  The pass a request ended in has no following state and is left
    out (that block was never committed: nothing later read it)."""
    out = []
    for (start, state), (then_start, then) in zip(states, states[1:]):
        masked = state == mask_id
        if then_start == start and masked.any():
            made = np.flatnonzero(masked & (then != mask_id))
            out.append((start, tuple(state.tolist()),
                        tuple((int(j), int(then[j])) for j in made)))
    return out


# What the passes of every request an engine of this process finished were
# given (``TimedPasses.by_request``), for the comparison that runs after the
# engine is released.
_STATES: dict = {}


def serve(cfg: dict, traffic: dict, chips: int, seed: int
          ) -> sparse.ServedSparse:
    if chips != 1:
        raise ValueError("sdar_moe_serve serves one pipeline stage on one "
                         "chip")
    mcfg = model_config(cfg, traffic)
    scfg = serving_config(cfg, traffic)
    model = Transformer(mcfg)
    slots, max_len, buckets = scfg.num_slots, scfg.max_seq_len, scfg.buckets
    block = mcfg.attention_block
    params = program_params(cfg, seed_key(seed))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    backend = TransformerBackend(model, params, mcfg, slots, max_len)
    del params
    timed = TimedPasses(backend, scfg.confidence_threshold)
    engine = ServingEngine(timed, scfg, clock=time.perf_counter,
                           on_complete=timed.finished)
    _STATES.clear()
    pool = jax.eval_shape(lambda: init_kv_cache(mcfg, slots, max_len))
    per_token = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                    for p in pool) // (slots * max_len)
    notes: dict = {"flash_prefill": backend.flash_prefill}
    gen = generation(cfg, traffic)
    plan = {"experts": cfg["num_experts"], "experts_held": cfg["num_experts"],
            "held_from": 0, "experts_per_token": cfg["num_experts_per_tok"],
            "shared_experts": 0, "selection": "softmax",
            "norm_topk_prob": cfg["norm_topk_prob"],
            "layers": {"sparse": cfg["num_hidden_layers"]}, "slots": slots,
            "pairs_a_pass": slots * block * cfg["num_experts_per_tok"]}

    def warm() -> None:
        def ids(n: int) -> list[int]:
            return [int(t) for t in np.arange(n) % mcfg.mask_token_id]

        for b in buckets:               # compiles each bucket, and the pass
            engine.submit(ids(min(b, max_len - 2 * block)), 3)
        engine.run_until_idle()
        # unloaded, on the programs now compiled: what the mix's two limits
        # were set from, read again in every run
        del timed.log[:]
        first = []
        for b in buckets:
            req = engine.submit(ids(min(b, max_len - 2 * block)), 2)
            engine.run_until_idle()
            first.append((b, 1e3 * req.ttft_s))
        notes["unloaded_prefill_ms_by_bucket"] = {
            e[3]: round(1e3 * (e[2] - e[1]), 3) for e in timed.log
            if e[0] == "prefill"}
        notes["unloaded_ttft_ms_by_bucket"] = {
            b: round(ms, 3) for b, ms in first}
        notes["unloaded_ttft_ms_longest_bucket"] = round(first[-1][1], 3)
        for _ in range(slots):
            engine.submit(ids(buckets[0]), 3 * block)
        engine.run_until_idle()
        full = [1e3 * (e[2] - e[1]) for e in timed.log
                if e[0] == "decode" and e[3] == slots]
        notes["unloaded_decode_ms_every_slot_full"] = statistics.median(full)
        timed.forget()

    def release() -> None:
        # of every call since the programs were built, warm-up and all
        c = engine.counters
        print("moe: " + json.dumps({
            **plan, **backend.moe_counters,
            "held_pair_share_pct": 100.0 * backend.moe_counters["held_pairs"]
            / max(backend.moe_counters["pairs"], 1)}))
        print("denoise: " + json.dumps({
            **{k: gen[k] for k in ("block_length", "denoising_steps",
                                   "remasking", "confidence_threshold")},
            **{k: c[k] for k in ("denoise_passes", "commit_passes",
                                 "tokens_final", "tokens_per_pass")},
            # masked positions whose confidence passed the threshold, since
            # the warm-up: what the dynamic rule would have made final more
            "above_threshold": timed.above_threshold}))
        _STATES.update(timed.by_request)
        backend.kk = backend.vv = backend.params = None

    def decode_scopes():
        blocks = jax.ShapeDtypeStruct((slots, block), jnp.int32)
        i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
        live = jax.ShapeDtypeStruct((slots,), jnp.bool_)
        return scopes.table_of(
            backend._decode.lower(shapes, *pool, blocks, i32, live).compile())

    def prefill_scopes(bucket: int):
        padded = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        return scopes.table_of(
            backend._prefill.lower(shapes, *pool, padded, 1, 0).compile())

    return sparse.ServedSparse(
        engine=engine, warm=warm, release=release,
        compare=functools.partial(compare_passes, cfg, traffic),
        # the bound of the ids the harness draws: no prompt holds a mask
        vocab_size=mcfg.mask_token_id,
        parameters=n_params, num_slots=slots, kv_bytes_per_token=per_token,
        program_names={"decode": "jit__block_decode_fn",
                       "prefill": "jit__prefill_fn"},
        decode_scopes=decode_scopes, notes=notes,
        prefill_scopes=prefill_scopes)


def sample_blocks(passes: list, seed: int, how_many: int = 8) -> list:
    """Of a request's denoising passes ``[(start, state, made), ...]`` those
    of: its first block (it holds the prompt's tail), its last committed
    block (the one before the block it ended in), and others drawn by the
    seed, ``how_many`` blocks in all; every pass of each."""
    starts = list(dict.fromkeys(p[0] for p in passes))
    picked = starts[:1] + starts[-2:-1]
    rng = np.random.default_rng(np.random.SeedSequence([abs(int(seed)), 56]))
    picked += [starts[int(k)] for k in rng.permutation(len(starts))]
    kept = set(list(dict.fromkeys(picked))[:how_many])
    return [p for p in passes if p[0] in kept]


_PROGRAMS: dict = {}


def _program(name: str, make, *key):
    if (name, *key) not in _PROGRAMS:
        _PROGRAMS[name, *key] = make()
    return _PROGRAMS[name, *key]


@jax.jit
def _judge(logits, tokens, mask_id, masked, chosen, count):
    """``logits`` [N, B, V] the reference's of N block states; ``tokens``
    [N, B] what the judged side made final there (anything elsewhere),
    ``masked`` [N, B] the state's masks, ``chosen`` [N, B] the positions it
    made final, ``count`` [N] how many.  Returns (a) [N, B], the token's gap
    below the reference's best non-mask token in the position's standard
    deviations (0 where nothing was made final), (b) [N], the reference's
    log-confidence at its own ``count`` best masked positions less that at
    the chosen ones, rank by rank, the widest, and (b) again for a WRONG
    rule, the ``count`` leftmost masked positions in the chosen ones' place
    (what filling a block left to right would read: (b)'s own control)."""
    _, conf = reference.confidences(logits, mask_id)            # [N, B]
    kept = logits.at[..., mask_id].set(-jnp.inf)
    picked = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    gap = (jnp.max(kept, axis=-1) - picked) / jnp.std(logits, axis=-1)
    gap = jnp.where(chosen, gap, 0.0)
    best = -jnp.sort(-jnp.where(masked, conf, -jnp.inf), axis=-1)
    took = -jnp.sort(-jnp.where(chosen, conf, -jnp.inf), axis=-1)
    ranks = jnp.arange(logits.shape[1])[None, :] < count[:, None]
    leftmost = masked & (jnp.cumsum(masked, axis=-1) <= count[:, None])
    left = -jnp.sort(-jnp.where(leftmost, conf, -jnp.inf), axis=-1)
    widest = lambda other: jnp.max(  # noqa: E731
        jnp.where(ranks, best - other, 0.0), axis=-1)
    return gap, widest(took), widest(left)


def _stood_in(logits, mask_id, masked, count):
    """What the control makes final of the states: its own best tokens at
    its ``count`` most confident masked positions (the static rule's
    choice, by the control's logits)."""
    tokens, conf = reference.confidences(logits, mask_id)
    order = jnp.argsort(-jnp.where(masked, conf, -jnp.inf), axis=-1)
    rank = jnp.argsort(order, axis=-1)
    return tokens.astype(jnp.int32), masked & (rank < count[:, None])


def compare_served(cfg, traffic, finished, seed, control=None) -> list[dict]:
    """What ``benchmarks/control.py`` asks a family for, one check: (a), as
    every served family's; (b) of the same reading is printed beside it, on
    a ``confidence_gap:`` line (:func:`compare_passes` makes both)."""
    gap, confidence = compare_passes(cfg, traffic, finished, seed, control)
    print("confidence_gap: " + json.dumps({
        "seed": seed, "control": None if control is None
        else jnp.dtype(control).name, **confidence}), flush=True)
    return [gap]


def compare_passes(cfg, traffic, finished, seed, control=None) -> list[dict]:
    """The comparison of a run (the two numbers above), over the longest
    ``compare_requests`` of ``finished``.  ``control`` is None in every run
    of the benchmark: judged is what the passes made final, by the states
    the backend was given (:func:`passes_of`).  Given an operand type (``benchmarks/control.py`` and the tests
    give ``jnp.float8_e4m3fn``, the step below the configuration's
    bfloat16), the reference computed with operands of that type stands in
    the program's place: for the same states, the tokens IT puts first at
    the positions IT is most confident of are judged by the same code and
    limits."""
    gen = generation(cfg, traffic)
    block, mask_id = int(gen["block_length"]), int(gen["mask_token_id"])
    logged = [(p, s, passes_of(_STATES.get(_ids(p, s), []), mask_id))
              for p, s in finished]
    logged = [x for x in logged if x[2]]
    logged.sort(key=lambda x: -(len(x[0]) + len(x[1])))
    chosen_requests = logged[:int(traffic["compare_requests"])]
    weights = draw(cfg, seed_key(seed)) if chosen_requests else None
    numbers = json.dumps(_numbers(cfg), sort_keys=True)
    max_len = int(traffic["max_seq_len"])
    query_block = max(max_len // 42, 1)      # the pads are multiples of it
    widest_gap = widest_conf = 0.0
    sum_conf = sum_left = 0.0
    states_judged = tokens_judged = 0
    by_request = []
    for prompt, served, passes in chosen_requests:
        passes = sample_blocks(passes, seed)
        # the final sequence up to the last sampled block's start, padded
        seq = np.concatenate([prompt, served]).astype(np.int32)
        need = max(p[0] for p in passes)
        pad = next((m * query_block for m in (6, 12, 24, 42)
                    if m * query_block >= need),
                   -(-need // query_block) * query_block)
        padded = np.zeros(pad, np.int32)
        padded[:min(len(seq), pad)] = seq[:pad]
        n = -(-len(passes) // 32) * 32
        starts = np.zeros(n, np.int32)
        states = np.full((n, block), mask_id, np.int32)
        tokens = np.zeros((n, block), np.int32)
        chosen = np.zeros((n, block), bool)
        for i, (start, state, made) in enumerate(passes):
            starts[i], states[i] = start, state
            for j, token in made:
                tokens[i, j], chosen[i, j] = token, True
        masked = states == mask_id
        masked[len(passes):] = False
        count = chosen.sum(axis=-1).astype(np.int32)

        def logits_of(operand_dtype):
            before = _program(
                "sequence", lambda: jax.jit(
                    lambda w, t: reference.sequence(
                        w, t, cfg, block, query_block, operand_dtype)[1:]),
                numbers, pad, operand_dtype)
            states_program = _program(
                "states", lambda: jax.jit(
                    lambda w, k, v, at, st: reference.block_logits(
                        w, k, v, at, st, cfg, operand_dtype)),
                numbers, pad, n, operand_dtype)
            keys, values = before(weights, padded)
            return states_program(weights, keys, values, starts, states)

        logits = logits_of(None)
        if control is not None:
            tokens, chosen = _stood_in(logits_of(control), mask_id,
                                       jnp.asarray(masked),
                                       jnp.asarray(count))
        gap, conf_gap, left_gap = (np.asarray(x) for x in _judge(
            logits, jnp.asarray(tokens), mask_id, jnp.asarray(masked),
            jnp.asarray(chosen), jnp.asarray(count)))
        widest_gap = max(widest_gap, float(gap.max()))
        widest_conf = max(widest_conf, float(conf_gap.max()))
        sum_conf += float(conf_gap[:len(passes)].sum())
        sum_left += float(left_gap[:len(passes)].sum())
        states_judged += len(passes)
        tokens_judged += int(count.sum())
        by_request.append([len(prompt), len(served), len(passes),
                           round(float(gap.max()), 4),
                           round(float(conf_gap[:len(passes)].mean()), 5)])
        del logits
    # nothing finished is nothing shown: a reading no limit admits
    nothing = not chosen_requests
    out = [compare.check("served_token_gap_below_reference_best",
                         1e9 if nothing else widest_gap, GAP_LIMIT),
           compare.check("chosen_position_confidence_gap",
                         1e9 if nothing else sum_conf / states_judged,
                         CONFIDENCE_GAP_LIMIT)]
    # (b)'s control, from the same states: a block filled left to right
    out[1].update(widest=widest_conf, leftmost_rule_gap=sum_left
                  / max(states_judged, 1))
    out[0].update(requests=len(chosen_requests), tokens=tokens_judged,
                  states=states_judged,
                  longest=max((len(p) + len(s)
                               for p, s, _ in chosen_requests), default=0),
                  # [prompt, served, passes judged, widest (a), mean (b)]
                  by_request=by_request)
    return out
