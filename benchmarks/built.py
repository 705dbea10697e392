"""What a family's ``build(config, traffic, chips, seed)`` hands the harness."""

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
