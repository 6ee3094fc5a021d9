"""Hook protocol for the training loop.

Reference parity: tensor2robot `hooks/hook_builder.py` — estimator
`SessionRunHook`s, chiefly the async-export-on-checkpoint path
(SURVEY.md §3 "Hooks"). The JAX trainer has no session, so hooks get
explicit callbacks at well-defined loop points.
"""

from __future__ import annotations

from typing import Any, Iterable, Optional


class Hook:
  """Base hook: override any subset of the callbacks."""

  # Trainers inspect this to detect the ONLINE regime (actors feeding
  # replay concurrently with training): it changes data-plane defaults
  # like the prefetch lookahead depth (sampling-lead vs throughput).
  drives_online_collection: bool = False

  def begin(self, model, model_dir: str) -> None:
    """Called once before the first step."""

  def after_step(self, step: int, metrics: dict) -> None:
    """Called after every train step (metrics are device arrays), for
    every step in turn. The train loop runs one dispatch ahead
    (`train_loop.TrainLoop`): the call for `step` may come while the
    dispatch after it executes, so `metrics` are that step's, and a
    hook that raises ends the run with one more dispatch enqueued. A
    hook that declares `drives_online_collection` gets each call
    before the next dispatch is enqueued."""

  def after_checkpoint(self, step: int, state: Any,
                       model_dir: str) -> None:
    """Called after a checkpoint save is initiated at `step`. `state`
    is the loop's snapshot of the state after exactly `step`: arrays
    of its own on the device, which no later dispatch donates; keep
    them and they stay allocated."""

  def end(self, step: int, state: Any, model_dir: str) -> None:
    """Called once after training finishes."""


class HookList(Hook):
  """Fans callbacks out to a list of hooks."""

  def __init__(self, hooks: Optional[Iterable[Hook]] = None):
    self._hooks = list(hooks or [])

  def append(self, hook: Hook) -> None:
    self._hooks.append(hook)

  @property
  def drives_online_collection(self) -> bool:  # type: ignore[override]
    return any(getattr(h, "drives_online_collection", False)
               for h in self._hooks)

  def begin(self, model, model_dir):
    for h in self._hooks:
      h.begin(model, model_dir)

  def after_step(self, step, metrics):
    for h in self._hooks:
      h.after_step(step, metrics)

  def after_checkpoint(self, step, state, model_dir):
    for h in self._hooks:
      h.after_checkpoint(step, state, model_dir)

  def end(self, step, state, model_dir):
    for h in self._hooks:
      h.end(step, state, model_dir)
