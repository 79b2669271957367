"""Uniform optimizer facade: name -> (init, update) with clipping + schedule.

``make_optimizer("adamw" | "adafactor", schedule, ...)`` returns an
:class:`Optimizer` whose ``init``/``update`` close over the hyperparameters,
so the train step only ever sees ``opt.init(params)`` and
``opt.update(params, grads, state, step)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from repro.optim.adafactor import adafactor_init, adafactor_update
from repro.optim.adamw import adamw_init, adamw_update
from repro.optim.clipping import clip_by_global_norm
from repro.optim.schedules import Schedule, constant_schedule

__all__ = ["Optimizer", "make_optimizer"]


@dataclasses.dataclass(frozen=True)
class Optimizer:
    name: str
    init: Callable[[Any], Any]
    update: Callable[..., tuple]  # (params, grads, state) -> (params, state, metrics)
    schedule: Schedule


def make_optimizer(
    name: str = "adamw",
    schedule: Schedule | None = None,
    max_grad_norm: float | None = 1.0,
    **hyper,
) -> Optimizer:
    schedule = schedule or constant_schedule(3e-4)

    if name == "adamw":
        init_fn, update_fn = adamw_init, adamw_update
    elif name == "adafactor":
        init_fn, update_fn = adafactor_init, adafactor_update
    else:
        raise ValueError(f"unknown optimizer {name!r}")

    @jax.named_scope("optimizer")
    def update(params, grads, state):
        lr = schedule(state.step)
        metrics = {"lr": lr}
        if max_grad_norm is not None:
            grads, norm = clip_by_global_norm(grads, max_grad_norm)
            metrics["grad_norm"] = norm
        new_params, new_state = update_fn(params, grads, state, lr, **hyper)
        return new_params, new_state, metrics

    return Optimizer(name=name, init=init_fn, update=update, schedule=schedule)
