"""What a family's ``build(config, traffic, chips, seed)`` (a model that is
trained) or ``serve(config, traffic, chips, seed)`` (one that is served)
hands the harness."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Built:
    # () -> the model's state (parameters, and what else the model keeps),
    # made on the device by one jitted call from the seed, on the mesh
    init_model: Callable[[], Any]
    # model state -> the train state the step donates (adds the optimizer's)
    init_train: Callable[[Any], Any]
    # jax.jit(hvd.shard(...), donate_argnums=(0,)): (state, *batch) ->
    # (state, loss); one call may hold several optimizer steps
    step: Any
    # host batches (global, one tuple of numpy leaves a call), cycled
    pool: list[tuple]
    batch_shardings: tuple
    # tokens or images one call trains on, over all chips
    units_per_call: int
    steps_per_call: int
    # forward and backward, nothing recomputed, per token or image
    flops_per_unit: float
    # model state -> [{"name", "error", "tolerance", "ok"}, ...]
    compare: Callable[[Any], list[dict]]
    # flash-kernel calls of one optimizer step on one chip, as shapes
    flash_calls: list[dict]
    notes: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Served:
    # horovod_tpu.serving.ServingEngine, its clock time.perf_counter, over
    # benchmarks.serving.Timed(the backend): what the window drives
    engine: Any
    # () -> None: compiles every prefill bucket and the decode program and
    # runs each once, so that nothing compiles inside the window
    warm: Callable[[], None]
    # () -> None: the cache and the program's weights leave the chip
    release: Callable[[], None]
    # ([(prompt ids, served ids), ...] of requests the window finished,
    # seed) -> [{"name", "error", "tolerance", "ok"}, ...]; after release()
    compare: Callable[[list[tuple], int], list[dict]]
    vocab_size: int
    parameters: int
    num_slots: int
    # bytes of K and V one cached token holds, over all layers
    kv_bytes_per_token: int
    # {"decode": ..., "prefill": ...}: what a trace's ``XLA Modules`` line
    # calls the backend's programs
    program_names: dict
    # () -> the decode program's scope table (instruction -> what the
    # program called it), or None; asked of a traced run on the chip alone
    decode_scopes: Callable[[], Any]
    notes: dict = dataclasses.field(default_factory=dict)
