"""Macro-scale training loop, orchestrated by the paper's IDAG machinery.

The instruction-graph runtime from ``repro.core`` schedules the *host-side*
stages of each training step — data prefetch into a staging ring, the jitted
``train_step`` dispatch, and asynchronous checkpoint I/O — as tasks over
virtual buffers.  The same dependency analysis that overlaps coherence
copies with kernels in the micro runtime here overlaps batch generation and
checkpoint writes with device compute:

  * ``stage[t % depth]``   written by prefetch task t, read by step task t —
    the WAR hazard between step t and prefetch t+depth is exactly the ring
    dependency the TDAG derives from the accessors;
  * checkpoint tasks read a ``ckpt_token`` buffer that step tasks write,
    serializing snapshots against parameter updates without blocking
    subsequent steps (the save itself is async in CheckpointManager).

Each step task dispatches one ``jax.jit`` step on JAX's default device (a
TPU chip where one is attached, else the CPU), donating the parameters and
optimizer state it replaces.  The loop spans one device; inside-step
distribution belongs to XLA (see DESIGN.md §2).

Every stage of a step is a span in the flight recorder
(``repro.core.tracing.flight_recorder``, or the ``tracer=`` given), each
carrying its ``step`` and mirrored into the profiler's trace when a
profiler session runs:

  * ``train.run`` — one ``run``: its Runtime's start, every task, the final
    sync and the Runtime's shutdown;
  * ``train.prefetch`` — the prefetch task: ``data.batch`` (the pipeline's
    ``local_batch``) and the write into ``stage``;
  * ``train.step`` — the step task (the profiler's step annotation):
    ``train.stage_read`` (the batch out of ``stage``), ``train.dispatch``
    (the jitted step enqueued) and ``train.loss_wait`` (the wait for the
    previous step's loss, the one host sync per step), then reporting that
    loss.  The wait comes after this step is enqueued, so the device has
    the next step queued whenever it finishes one, and the host's turn
    between steps (the wake-up on the loss, the Runtime issuing the next
    task, the dispatch) is hidden behind the step; the last step's loss is
    waited for once the Runtime has synced;
  * ``train.ckpt`` — the checkpoint task;
  * counter ``runtime.instructions`` — instructions the Runtime executed,
    one sample per ``run``.
"""

from __future__ import annotations

import functools
import queue as _queue
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import (Box, Runtime, fixed, one_to_one, read, read_write,
                        write)
from repro.core.task_graph import TaskType
from repro.core.tracing import Tracer, flight_recorder
from repro.data import SyntheticLMData
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw_init


@dataclass
class TrainMetrics:
    steps: list = field(default_factory=list)
    losses: list = field(default_factory=list)
    restarts: int = 0

    def log(self, step, loss):
        self.steps.append(int(step))
        self.losses.append(float(loss))


def _annotation(name: str, step: int):
    """The profiler's annotation of a trainer span.  The step task is the
    profiler's step, numbered, so TensorBoard's step view reads it too; the
    other spans leave the number to the flight recorder, which is cheaper."""
    if name == "train.step":
        return jax.profiler.StepTraceAnnotation(name, step_num=step)
    return jax.profiler.TraceAnnotation(name)


class TrainLoop:
    def __init__(self, cfg, *, global_batch: int, seq_len: int,
                 ckpt_dir=None, ckpt_interval: int = 50, lr: float = 3e-4,
                 prefetch_depth: int = 2, seed: int = 0,
                 tracer: Optional[Tracer] = None):
        self.cfg = cfg
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.model = build_model(cfg)
        self.data = SyntheticLMData(cfg, global_batch, seq_len, seed=seed)
        self.depth = prefetch_depth
        self.lr = lr
        self.ckpt = (CheckpointManager(ckpt_dir, interval=ckpt_interval)
                     if ckpt_dir else None)
        self.train_step = jax.jit(make_train_step(self.model, lr=lr),
                                  donate_argnums=(0, 1))
        self.tracer = tracer if tracer is not None else flight_recorder()
        # scope(lane, name, step=t); a lane per task, as tasks overlap
        self._scope = functools.partial(self.tracer.scope,
                                        annotate=_annotation)

    # -- state ------------------------------------------------------------------
    def init_state(self, seed: int = 0):
        params = self.model.init(jax.random.PRNGKey(seed))
        return {"params": params, "opt": adamw_init(params)}

    def restore_or_init(self):
        """Checkpoints are taken AFTER step t completes, so a restore from
        step t resumes at t+1."""
        if self.ckpt is not None and self.ckpt.latest is not None:
            step, state = self.ckpt.restore_or_init(lambda: self.init_state())
            return step + 1, state
        return 0, self.init_state()

    # -- the IDAG-orchestrated run ------------------------------------------------
    def run(self, num_steps: int, *, start_step: Optional[int] = None,
            state=None, metrics: Optional[TrainMetrics] = None,
            fail_at: Optional[int] = None) -> tuple[int, dict, TrainMetrics]:
        metrics = metrics or TrainMetrics()
        if state is None:
            start_step, state = self.restore_or_init()
        assert start_step is not None
        holder = {"state": state}
        results: "_queue.SimpleQueue" = _queue.SimpleQueue()

        try:
            self._run_body(num_steps, start_step, holder, results, fail_at)
        finally:
            # drain metrics and finish in-flight checkpoint I/O even on the
            # failure path — a committed step must be restorable immediately
            while True:
                try:
                    t, loss = results.get_nowait()
                    metrics.log(t, loss)
                except _queue.Empty:
                    break
            if self.ckpt is not None:
                self.ckpt.wait()
        return start_step + num_steps, holder["state"], metrics

    def _run_body(self, num_steps, start_step, holder, results, fail_at):
        scope = self._scope
        pending = []                   # the (step, loss) not yet on the host

        def settle():
            if pending:
                t, loss = pending.pop()
                results.put((t, float(loss)))

        with scope("run", "train.run", step=start_step), \
                Runtime(num_nodes=1, devices_per_node=1) as rt:
            B = self.global_batch
            stage = rt.buffer((self.depth, B, self.seq_len), dtype=np.int32,
                              name="stage",
                              init=np.zeros((self.depth, B, self.seq_len),
                                            np.int32))
            token = rt.buffer((1,), name="ckpt_token", init=np.zeros(1))

            def slot_region(t):
                return Box((t % self.depth, 0, 0),
                           (t % self.depth + 1, B, self.seq_len))

            for t in range(start_step, start_step + num_steps):
                def prefetch(chunk, v, t=t):
                    with scope("prefetch", "train.prefetch", step=t):
                        with scope("prefetch", "data.batch", step=t):
                            batch = self.data.local_batch(t)
                        v.set(slot_region(t), batch["tokens"][None])

                rt.submit(f"prefetch{t}", (1,),
                          [write(stage, fixed(slot_region(t)))],
                          prefetch, ttype=TaskType.HOST)

                def step_fn(chunk, v, tok, t=t):
                    with scope("step", "train.step", step=t):
                        try:
                            with scope("step", "train.stage_read", step=t):
                                toks = np.asarray(v.get(slot_region(t))[0])
                            if fail_at is not None and t == fail_at:
                                raise RuntimeError(
                                    f"injected failure at step {t}")
                            batch = {"tokens": toks, "labels": toks}
                            s = holder["state"]
                            with scope("step", "train.dispatch", step=t):
                                p, o, m = self.train_step(s["params"],
                                                          s["opt"], batch)
                            holder["state"] = {"params": p, "opt": o}
                        finally:
                            with scope("step", "train.loss_wait", step=t):
                                settle()
                        pending.append((t, m["loss"]))
                        tok[0] = float(t)

                rt.submit(f"step{t}", (1,),
                          [read(stage, fixed(slot_region(t))),
                           read_write(token, one_to_one())],
                          step_fn, ttype=TaskType.HOST)

                if self.ckpt is not None and self.ckpt.should_save(t):
                    def ckpt_fn(chunk, tok, t=t):
                        with scope("ckpt", "train.ckpt", step=t):
                            self.ckpt.save(t, holder["state"])

                    rt.submit(f"ckpt{t}", (1,),
                              [read(token, one_to_one())],
                              ckpt_fn, ttype=TaskType.HOST)
            rt.sync(timeout=600)
            settle()
            self.tracer.counter("runtime.instructions",
                                rt.metrics()["executor"][0]["done"])


def train(cfg, *, steps: int, global_batch: int, seq_len: int,
          ckpt_dir=None, **kw) -> TrainMetrics:
    loop = TrainLoop(cfg, global_batch=global_batch, seq_len=seq_len,
                     ckpt_dir=ckpt_dir, **kw)
    _, _, metrics = loop.run(steps)
    return metrics
