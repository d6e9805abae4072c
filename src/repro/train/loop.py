"""Training loop: data -> step -> checkpoint, with restart & stragglers.

Fault-tolerance behaviour:
  * checkpoint every ``ckpt_every`` steps via the parallel single-file
    writer (async by default — the paper's opt-2 pattern: the loop blocks
    only on the snapshot hand-off);
  * checkpoints carry params, optimizer state AND the loader cursor, so a
    restarted run continues on the exact next batch;
  * on construction the loop restores the latest committed checkpoint if
    one exists (crash-restart is the default path, not a special case);
  * straggler mitigation: per-step wall time (input, placement, step and
    loss fetch) is tracked against the median of the last
    ``STRAGGLER_WINDOW`` steps; a step slower than ``straggler_factor``x
    that median fires the
    ``on_straggler`` hook (at fleet scale: re-shard that host's data and
    deprioritize it; here the hook records the event and the test asserts
    the detection fires).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.core import stats
from repro.core.stats import span
from repro.models.registry import ModelBundle
from repro.pipeline import PackedLoader

from .optimizer import AdamW, make_optimizer
from .step import init_train_state, make_train_step

#: steps whose median the straggler check compares against
STRAGGLER_WINDOW = 20


@dataclass
class LoopConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_async: bool = True
    log_every: int = 10
    keep_ckpts: int = 3
    straggler_factor: float = 3.0
    grad_compression: bool = False
    microbatches: int = 1


@dataclass
class StepEvent:
    step: int
    loss: float
    wall_s: float
    straggler: bool = False
    #: the step's metrics beyond the loss: ``ce`` and ``aux``, and a MoE
    #: model's counters ``moe.rows_local``, ``moe.load_max_over_mean``,
    #: ``moe.bias_abs_mean`` and the per-layer picks per expert
    #: ``moe.picks`` (an array)
    counters: Dict[str, Any] = field(default_factory=dict)


class TrainLoop:
    def __init__(
        self,
        bundle: ModelBundle,
        mesh,
        loader: PackedLoader,
        ckpt_dir: str,
        config: Optional[LoopConfig] = None,
        optimizer: Optional[AdamW] = None,
        on_straggler: Optional[Callable[[StepEvent], None]] = None,
    ):
        self.bundle = bundle
        self.mesh = mesh
        self.loader = loader
        self.config = config or LoopConfig()
        self.optimizer = optimizer or make_optimizer()
        self.mgr = CheckpointManager(ckpt_dir, keep=self.config.keep_ckpts)
        self.on_straggler = on_straggler
        self.history: List[StepEvent] = []
        self._step_times: deque = deque(maxlen=STRAGGLER_WINDOW)

        jitted_for, shardings = make_train_step(
            bundle, mesh, optimizer=self.optimizer,
            grad_compression=self.config.grad_compression,
            microbatches=self.config.microbatches,
        )
        self._jitted_for = jitted_for
        self._in_shardings_for = shardings["in_shardings_for"]
        self._step_fn = None
        self._batch_sh = None
        self.step = 0

        latest = self.mgr.latest_step()
        if latest is not None:
            self._restore(latest)
        else:
            self.params, self.opt_state, self.err_state = init_train_state(
                bundle, mesh, optimizer=self.optimizer,
                grad_compression=self.config.grad_compression,
            )

    # -- checkpoint integration ------------------------------------------------

    def _state_tree(self) -> Dict:
        ld = self.loader.state()  # device engine syncs its leftover here
        return {
            "params": self.params,
            "opt": {"step": self.opt_state.step, "m": self.opt_state.m,
                    "v": self.opt_state.v},
            "err": self.err_state,
            "loader": {
                "entry_cursor": np.asarray(ld["entry_cursor"]),
                "leftover": np.asarray(ld["leftover"], np.int32),
            },
        }

    def _save(self) -> None:
        tree = self._state_tree()
        meta = {"train_step": self.step}
        if self.config.ckpt_async:
            self.mgr.save_async(self.step, tree, meta)
        else:
            self.mgr.save(self.step, tree, meta)

    def _restore(self, step: int) -> None:
        from .optimizer import AdamWState

        target = None  # names-based reconstruction
        tree, meta = self.mgr.restore(step)
        self.params = tree["params"]
        o = tree["opt"]
        self.opt_state = AdamWState(o["step"], o["m"], o["v"])
        self.err_state = tree["err"]
        self.loader.load_state({
            "entry_cursor": int(np.asarray(tree["loader"]["entry_cursor"])),
            "leftover": np.asarray(tree["loader"]["leftover"], np.int32),
        })
        self.step = int(meta["train_step"])

    # -- run ----------------------------------------------------------------

    def place(self, batch: Dict) -> Dict:
        """Put a loader batch (host or device arrays) on the step's batch
        shardings: the device engine decodes onto one device, and a mesh
        of several takes its shards from there."""
        if self._step_fn is None:
            shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
            self._step_fn = self._jitted_for(shapes)
            self._batch_sh = self._in_shardings_for(shapes)[3]
        return jax.device_put(batch, self._batch_sh)

    def run(self, steps: Optional[int] = None) -> List[StepEvent]:
        """Run ``steps`` steps.  Each is the ``train.step`` span (a
        ``StepTraceAnnotation`` numbered by the step it completes), with
        ``train.next_batch``, ``train.place``, ``train.dispatch``,
        ``train.loss_wait`` and ``train.bookkeeping`` inside it.  Every
        metric the step returns beyond the loss is fetched with it and
        kept in the step's :class:`StepEvent` and, while a profiler
        session is active, in a ``train.counters`` record."""
        steps = steps if steps is not None else self.config.steps
        batches = self.loader.batches()
        target = self.step + steps
        while self.step < target:
            k = self.step + 1
            with span("train.step", k, timed=True, step=True) as st:
                with span("train.next_batch", k):
                    batch = next(batches)
                with span("train.place", k):
                    jb = self.place(batch)
                with span("train.dispatch", k):
                    (self.params, self.opt_state, self.err_state,
                     metrics) = self._step_fn(self.params, self.opt_state,
                                              self.err_state, jb)
                with span("train.loss_wait", k, timed=True) as lw:
                    got = jax.device_get(metrics)
                    loss = float(got.pop("loss"))
                    counters = {n: float(x) if np.ndim(x) == 0 else x
                                for n, x in got.items()}
                self.step = k
                with span("train.bookkeeping", k):
                    if counters:
                        stats.note("train.counters", (k, counters))
                    self._bookkeep(loss, (lw.t1 - st.t0) / 1e9, counters)
        self.mgr.wait()
        return self.history

    def _bookkeep(self, loss: float, wall: float, counters: Dict) -> None:
        """Straggler check, history, logging and checkpointing after a
        step whose input, placement, dispatch and loss took ``wall``."""
        straggler = False
        if len(self._step_times) >= 5:
            med = float(np.median(self._step_times))
            straggler = wall > self.config.straggler_factor * med
        self._step_times.append(wall)
        ev = StepEvent(self.step, loss, wall, straggler, counters)
        self.history.append(ev)
        if straggler and self.on_straggler:
            self.on_straggler(ev)
        if self.step % self.config.log_every == 0:
            print(f"step {self.step:6d}  loss {loss:8.4f}  {wall*1e3:8.1f} ms",
                  flush=True)
        if self.step % self.config.ckpt_every == 0:
            self._save()
