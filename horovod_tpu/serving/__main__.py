"""``python -m horovod_tpu.serving`` — one serving replica per rank.

This is what ``run.py --serve`` launches: a continuous-batching replica
that joins the fleet's control plane (env-based rendezvous, identical to
a training rank), serves a self-generated Poisson workload, and prints a
one-line JSON report: the load generator's latencies, ``serving_stats()``
and, under ``"spans"``, ``ServingEngine.span_summary()``.  A relaunched
seat (``HVD_TPU_ELASTIC_JOIN=1``) rejoins via a JOIN ticket and pulls the weights from its ring neighbor
over the bulk data plane — no disk.

Knobs (utils/env.py table): ``HVD_TPU_SERVE_BACKEND`` (``transformer`` —
a small real model on the KV-cache decode path, or the model a JSON file of
``TransformerConfig`` fields describes, ``HVD_TPU_SERVE_MODEL`` — or
``stub``, the jax-free token automaton), ``HVD_TPU_SERVE_QPS``,
``HVD_TPU_SERVE_DURATION_S``, plus the scheduler shape knobs
``HVD_TPU_SERVE_SLOTS`` / ``_BUCKETS`` / ``_MAX_LEN`` and the fast-path
knobs ``HVD_TPU_SERVE_PREFIX_PAGES`` / ``_PAGE_TOKENS`` (the
transformer backend switches to the paged KV pool when the prefix
cache is on) / ``_SPEC_K``.
"""

from __future__ import annotations

import json
import os
import sys

from horovod_tpu import elastic
from horovod_tpu.core import engine as em
from horovod_tpu.core.engine import MembershipChanged, NativeEngine
from horovod_tpu.core.executors import local_executor
from horovod_tpu.serving import autoscale, loadgen
from horovod_tpu.serving.engine import (ServingConfig, ServingEngine,
                                        StubBackend, TransformerBackend)
from horovod_tpu.utils import env as env_knobs


def _make_backend(cfg: ServingConfig):
    if os.environ.get("HVD_TPU_SERVE_BACKEND", "transformer") == "stub":
        return StubBackend(cfg.num_slots), None
    import jax

    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)
    from horovod_tpu.utils import chip

    chip.enable_compile_cache()

    if env_knobs.serve_model():
        # a configuration's own model: every TransformerConfig field is the
        # file's to set (layer types, window, parallel block, experts held)
        with open(env_knobs.serve_model()) as f:
            mcfg = TransformerConfig.from_dict(
                {**json.load(f), "max_seq_len": cfg.max_seq_len})
    else:
        mcfg = TransformerConfig(vocab_size=256, num_layers=2, num_heads=2,
                                 head_dim=16, embed_dim=32, mlp_dim=64,
                                 max_seq_len=cfg.max_seq_len)
    model = Transformer(mcfg)
    toks = jax.numpy.zeros((1, cfg.buckets[0]), jax.numpy.int32)
    init = jax.jit(model.init) if env_knobs.serve_model() else model.init
    params = init(jax.random.PRNGKey(0), toks)
    if cfg.prefix_cache_pages > 0:
        from horovod_tpu.serving.engine import PagedTransformerBackend

        return PagedTransformerBackend(
            model, params, mcfg, cfg.num_slots, cfg.max_seq_len,
            cache_pages=cfg.prefix_cache_pages,
            page_size=cfg.page_size), params
    return TransformerBackend(model, params, mcfg, cfg.num_slots,
                              cfg.max_seq_len), params


def main() -> int:
    from horovod_tpu import dataplane

    rank = int(os.environ.get("JAX_PROCESS_ID", "0"))
    n = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    port = os.environ.get("HVD_TPU_COORDINATOR_PORT")
    eng = None
    if port is not None and n > 1:
        dataplane.ensure_listener()
        if os.environ.get("HVD_TPU_ELASTIC_JOIN") == "1":
            t = elastic.join("127.0.0.1", int(port), old_rank=rank,
                             timeout_s=60.0)
            host, cport = elastic.coordinator_endpoint("127.0.0.1",
                                                       int(port))
            eng = NativeEngine(t.assigned_rank, t.new_size,
                               executor=local_executor,
                               coordinator_host=host,
                               coordinator_port=cport, cycle_time_ms=2.0,
                               epoch=t.epoch)
        else:
            eng = NativeEngine(rank, n, executor=local_executor,
                               coordinator_host="127.0.0.1",
                               coordinator_port=int(port),
                               cycle_time_ms=2.0)
        elastic.attach(eng)
    cfg = ServingConfig.from_env()
    backend, params = _make_backend(cfg)
    if eng is not None and os.environ.get("HVD_TPU_ELASTIC_JOIN") == "1":
        snap = autoscale.pull_weights(eng, timeout_s=30.0)
        if snap is not None and hasattr(backend, "swap_params"):
            backend.swap_params(snap["state"])
            print(f"[serve r{eng.rank}] weights v{snap['step']} pulled "
                  "over data plane (no disk)", flush=True)
    serving = ServingEngine(backend, cfg, collective=eng)
    w = loadgen.Workload(qps=env_knobs.serve_qps(),
                         duration_s=env_knobs.serve_duration_s(),
                         seed=rank,
                         prompt_lens=tuple(
                             b - 2 for b in cfg.buckets[:3]),
                         vocab=backend.model.cfg.vocab_size
                         if hasattr(backend, "model") else 256)
    if eng is None:
        rep = loadgen.run_load(serving, w, max_wall_s=w.duration_s * 20)
    else:
        rep = _serve_fleet(serving, w, params)
    out = {"rank": rank, **rep, **serving.stats(),
           "spans": serving.span_summary()}
    print("SERVE_REPORT " + json.dumps(out), flush=True)
    if eng is not None:
        em.peek_engine().shutdown()
    return 0


def _serve_fleet(serving: ServingEngine, w: loadgen.Workload,
                 params) -> dict:
    """Multi-replica serve loop: each rank submits its own arrival stream
    but keeps ticking (the fleet collective must stay in lockstep) until
    EVERY replica has drained.  The drain rendezvous is a one-shot
    ``serving.drained`` collective announced when this rank empties and
    *polled* while ticking continues: the coordinator dispatches it only
    once all replicas announced, and dispatch order is identical on every
    rank, so poll() flips true after the same tick fleet-wide — a true
    barrier even under the single-process local executor, whose allreduce
    "sum" (and hence the tick vector's done_replicas count) never crosses
    ranks.  Membership changes reconfigure in place; on a grow, the
    joiner's ring neighbor donates the weights over the data plane."""
    import time

    import numpy as np

    from horovod_tpu.core.engine import OP_ALLREDUCE

    arrivals = loadgen.make_arrivals(w)
    # Rank 0 runs the live autoscale policy over the tick aggregates;
    # verdicts land as AUTOSCALE timeline instants and one stdout line
    # each, which the supervisor holding the fleet (run.py, an operator)
    # acts on by launching a joiner / retiring a seat.
    auto = autoscale.Autoscaler(autoscale.AutoscaleConfig.from_env(),
                                collective=serving.collective)
    t0 = serving.clock()
    done, i = [], 0
    drained_h = None
    deadline = t0 + w.duration_s * 20
    while True:
        now = serving.clock() - t0
        while i < len(arrivals) and arrivals[i][0] <= now:
            serving.submit(arrivals[i][1], arrivals[i][2])
            i += 1
        mine_done = (i >= len(arrivals) and not serving.queue
                     and serving._active_count() == 0)
        serving.done_flag = 1.0 if mine_done else 0.0
        try:
            done.extend(serving.step())
            if serving.collective.rank == 0 and not mine_done:
                verdict = auto.decide(
                    replicas=serving.collective.size,
                    queued=serving.fleet.get("queued", 0.0),
                    active_slots=serving.fleet.get("active", 0.0),
                    p99_ttft_ms=serving.stats()["ttft_p99_ms"])
                if verdict is not None:
                    print(f"AUTOSCALE {verdict} "
                          f"replicas={serving.collective.size}", flush=True)
            if mine_done and drained_h is None:
                drained_h = serving.collective.enqueue(
                    "serving.drained", np.zeros(1, np.float32),
                    OP_ALLREDUCE)
            if drained_h is not None and \
                    serving.collective.poll(drained_h):
                serving.collective.synchronize(drained_h)
                break
        except MembershipChanged:
            ev = elastic.reconfigure()
            serving.collective = em.peek_engine()
            auto.collective = serving.collective
            drained_h = None  # handle belonged to the replaced engine
            if ev.grew and serving.collective.rank == ev.new_size - 2:
                autoscale.ship_weights(serving.collective, ev.new_size - 1,
                                       1, params if params is not None
                                       else {"version": 1})
        if serving.clock() > deadline:
            break
        if mine_done:
            time.sleep(0.001)
    return loadgen.report(done, max(serving.clock() - t0, 1e-9),
                          offered=len(arrivals))


if __name__ == "__main__":
    sys.exit(main())
