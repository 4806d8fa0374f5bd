"""A training cell: the program's ``TrainLoop`` through set-up, a timed
window and the check against the plain reference.

Set-up builds one trainer for the cell's configuration and shape, makes
its weights and AdamW state on the device from the seed in one jitted
call, compiles the cell's one train-step shape (through the program's
persistent compile cache), then drives the trainer's own ``run`` through
the checked steps (their losses, first gradient and parameter change are
kept for the check) and a short warm-up that sets the window's length.
The window is one ``TrainLoop.run(n, ...)`` call.  After it, the
program's state is freed and the reference trains the checked steps
from the same seed and rows.

The benchmark wraps attributes of the trainer object and edits nothing
of the program: the data pipeline's ``loop.data.local_batch`` runs under
a host span, and the rows it feeds the checked steps are held against
the traffic generator's; ``loop.train_step`` is the compiled step under
host spans that also hands each step's loss to a thread noting when it
is ready on the device.  Python's garbage collections are host spans
too, so a step that stalls on the host shows whether one ran in it.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import queue
import statistics
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from checks import decide, readings
from reference import DOTS, leaf_norms_device, norms_dict, three_steps
from traffic import TokenBatches


def seed_key(seed: int):
    """A PRNG key from any non-negative seed below 2**63."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def arch_config(config: dict):
    """The program's configuration: the registry entry with the file's
    overrides.  Every program field the file names must agree with it,
    and so must each size the program derives (``derived`` maps
    ``cfg.<attr>`` or ``model.<attr>`` to the file's key for it)."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = dataclasses.replace(get_config(config["registry"]),
                              **config["overrides"])
    model = build_model(cfg)
    for f in dataclasses.fields(cfg):
        if f.name != "name" and f.name in config and \
                getattr(cfg, f.name) != config[f.name]:
            raise ValueError(f"{config['name']}: {f.name} is "
                             f"{getattr(cfg, f.name)!r} in the program, "
                             f"{config[f.name]!r} in the file")
    for path, key in config.get("derived", {}).items():
        obj, attr = path.split(".")
        got = getattr({"cfg": cfg, "model": model}[obj], attr)
        if got != config[key]:
            raise ValueError(f"{config['name']}: {path} is {got!r}, "
                             f"{key} is {config[key]!r} in the file")
    return cfg


class Spans:
    """Host spans around calls into the program's layers: kept as
    (name, start, end) on the host clock and written into the profiler's
    trace when one is running."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self._lock = threading.Lock()

    def call(self, name: str, fn, *a, **kw):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                t1 = time.perf_counter()
                with self._lock:
                    self.spans.append((name, t0, t1))

    def add(self, name: str, t0: float, t1: float):
        with self._lock:
            self.spans.append((name, t0, t1))

    def within(self, name: str, t0: float, t1: float) -> list[float]:
        return [b - a for n, a, b in self.spans
                if n == name and a >= t0 and b <= t1]

    def total(self, name: str, t0: float, t1: float) -> float:
        return sum(self.within(name, t0, t1))


class GcSpans:
    """Each of Python's garbage collections as a host span ``bench.gc``,
    in ``spans`` and in the profiler's trace."""

    def __init__(self, spans: Spans):
        self.spans = spans
        self._open = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            ann = jax.profiler.TraceAnnotation("bench.gc")
            ann.__enter__()
            self._open = (ann, time.perf_counter())
        elif self._open is not None:
            ann, t0 = self._open
            self._open = None
            ann.__exit__(None, None, None)
            self.spans.add("bench.gc", t0, time.perf_counter())

    def close(self):
        gc.callbacks.remove(self._on)


class Completions:
    """A thread that waits on each step's loss in turn and notes when it
    is ready: step times from one step's completion to the next, whether
    or not the trainer itself waits on the loss."""

    def __init__(self):
        self.times: list[float] = []
        self.error: BaseException | None = None
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        while (x := self._q.get()) is not None:
            if isinstance(x, _Marker):
                x.ev.set()
                continue
            try:
                x.block_until_ready()
            except Exception as e:  # a failed step: the drain reports it
                self.error = e
            self.times.append(time.perf_counter())

    def put(self, loss):
        self._q.put(loss)

    def drain(self) -> list[float]:
        """Wait for every loss handed in so far; return and clear the
        completion times."""
        done = threading.Event()
        self._q.put(_Marker(done))
        done.wait()
        if self.error is not None:
            raise RuntimeError("a step failed on the device") from self.error
        out, self.times = self.times, []
        return out

    def close(self):
        self._q.put(None)
        self._t.join()


class _Marker:
    def __init__(self, ev):
        self.ev = ev


class CompileCounter:
    """Backend compilations, counted through ``jax.monitoring``."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


class Trainer:
    """The program under test, set up for one cell and seed: the trainer,
    its state made on the device from the seed, and its one compiled
    step shape."""

    def __init__(self, cell, seed: int, log=print, t_start=None):
        from repro.launch.compile_cache import use_persistent_cache
        from repro.optim import adamw_init
        from repro.runtime import TrainLoop

        t_start = time.perf_counter() if t_start is None else t_start
        use_persistent_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        self.compiles = CompileCounter()
        self.dev = jax.devices()[0]
        tr = cell.traffic
        self.opt = tr["optimizer"]
        self.B, self.S = tr["batch"], tr["seq"]

        cfg = arch_config(cell.config)
        self.loop = loop = TrainLoop(cfg, global_batch=self.B, seq_len=self.S,
                                     lr=self.opt["lr"], seed=seed)
        self.rows = TokenBatches(tr, cfg.vocab_size, seed)
        self.spans = spans = Spans()
        self.gc = GcSpans(spans)
        # the rows the program's pipeline fed the checked steps
        self.fed = fed = {}
        n_fed = tr["checked_steps"]
        program_batch = loop.data.local_batch

        def local_batch(step, *a):
            out = spans.call("bench.prefetch", program_batch, step, *a)
            if step < n_fed:
                fed[step] = out
            return out

        loop.data.local_batch = local_batch

        def make_state(k):
            params = loop.model.init(k)
            return {"params": params, "opt": adamw_init(params)}

        self.state = jax.jit(make_state)(seed_key(seed))
        jax.block_until_ready(self.state)
        log(f"[setup] {time.perf_counter() - t_start:.2f} s: weights made")
        t0 = time.perf_counter()
        compiled = loop.train_step.lower(
            self.state["params"], self.state["opt"],
            loop.data.local_batch(0)).compile()
        self.compile_s = time.perf_counter() - t0
        log(f"[setup] {time.perf_counter() - t_start:.2f} s: step compiled "
            f"in {self.compile_s:.2f} s")
        self.done = done = Completions()

        def step(params, opt_state, batch):
            out = spans.call("bench.dispatch", compiled, params, opt_state,
                             batch)
            done.put(out[2]["loss"])
            return out

        loop.train_step = step
        self.next_step = 0

    def run(self, n: int, metrics=None):
        """``n`` steps through the trainer's own ``run``."""
        _, self.state, m = self.loop.run(n, start_step=self.next_step,
                                         state=self.state, metrics=metrics)
        self.next_step += n
        return m

    def checked_steps(self, n: int) -> dict:
        """The first ``n`` steps, with what the reference is compared on:
        their losses, the first clipped gradient (the first moment after
        one step, over 1 - b1) and the parameters' change, per leaf."""
        assert self.next_step == 0
        norms = jax.jit(leaf_norms_device)
        p0 = jax.device_get(self.state["params"])
        m = self.run(1)
        grad = {k: v / (1 - self.opt["b1"]) for k, v in
                norms_dict(norms(self.state["opt"]["m"])).items()}
        m = self.run(n - 1, metrics=m)
        update = norms_dict(jax.jit(lambda p, q: leaf_norms_device(
            jax.tree.map(jnp.subtract, p, q)))(self.state["params"], p0))
        self.done.drain()
        rows_wrong = 0
        for t in range(n):
            want = self.rows.tokens(t)
            got = self.fed.get(t, {})
            rows_wrong += not all(k in got and np.array_equal(got[k], want)
                                  for k in ("tokens", "labels"))
        return {"losses": list(m.losses), "grad": grad, "update": update,
                "rows_wrong": rows_wrong}

    def close(self):
        self.done.close()
        self.compiles.close()
        self.gc.close()
        del self.state, self.loop
        gc.collect()


def reference(cell, seed: int, steps: int, dot="exact", **kw) -> dict:
    data = TokenBatches(cell.traffic, cell.config["vocab_size"], seed)
    return three_steps(
        cell.model, cell.config, cell.traffic["optimizer"], seed_key(seed),
        [data.tokens(t) for t in range(steps)], DOTS[dot], steps=steps, **kw)


PROFILE = dict(python_tracer_level=0, host_tracer_level=2)
# a time on the host's clock is off by about half a millisecond, so a
# step time is taken over enough consecutive steps to span this
MIN_HOST_SPAN_S = 0.25


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        trace_dir=None, log=print) -> dict:
    """One run of a training cell.  Returns the numbers the result line
    is made from (see ``run.py``)."""
    tr = cell.traffic
    n_checked = tr["checked_steps"]
    t = Trainer(cell, seed, log, t_start)
    prog = t.checked_steps(n_checked)

    # warm-up: sets the window's length from the step time
    t.run(tr["warmup_steps"])
    ends = t.done.drain()
    step_s = statistics.median(b - a for a, b in zip(ends[:-1], ends[1:]))
    group = max(1, math.ceil(MIN_HOST_SPAN_S / step_s))
    n = group * max(1, round(seconds / (group * step_s)))
    start = t.next_step
    log(f"[setup] {time.perf_counter() - t_start:.2f} s: checked steps "
        f"done, warm-up step {step_s:.4f} s, window of {n} steps timed in "
        f"groups of {group}")
    compiles_before = t.compiles.n

    if trace:
        opts = jax.profiler.ProfileOptions()
        for k, v in PROFILE.items():
            setattr(opts, k, v)
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t_w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        mw = t.run(n)
    t_w1 = time.perf_counter()
    ends = t.done.drain()
    if trace:
        jax.profiler.stop_trace()
    window_compiles = t.compiles.n - compiles_before

    stats = t.dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        # a loaded program's temporaries are reserved apart from the
        # buffers in use, and the reservation lasts while it runs
        peak += stats.get("peak_bytes_reserved", 0)
    expected = list(range(start, start + n))
    wrong = sum(1 for a, b in zip(mw.steps, expected) if a != b)
    wrong += abs(len(mw.steps) - n)
    wrong += sum(1 for x in mw.losses if not math.isfinite(x))
    prog["window_steps_wrong"] = wrong
    if len(ends) != n:
        raise RuntimeError(f"{len(ends)} losses came back from {n} steps")
    marks = [t_w0] + ends[group - 1::group]
    steps_ms = [1e3 * (b - a) / group for a, b in zip(marks[:-1], marks[1:])]
    assert len(steps_ms) == n // group
    # what the host did in the slowest step, for a stall's cause
    i = max(range(len(steps_ms)), key=steps_ms.__getitem__)
    inside = {}
    for name, a, b in t.spans.spans:
        if a >= marks[i] and b <= marks[i + 1]:
            inside.setdefault(name, []).append(1e3 * (b - a))
    log(f"[run] slowest step {i}: {steps_ms[i]:.2f} ms; host spans in it "
        + ", ".join(f"{k} {len(v)}x, {sum(v):.1f} ms, the longest "
                    f"{max(v):.1f} ms" for k, v in sorted(inside.items()))
        + f"; device memory {stats}")
    out = {"attempted": n, "failed": wrong, "device": t.dev,
           "memory_peak": peak,
           "setup_s": t_w0 - t_start, "compile_s": t.compile_s,
           "window": (t_w0, t_w1), "steps": n, "steps_ms": steps_ms,
           "tokens": n * t.B * t.S, "spans": t.spans,
           "window_compiles": window_compiles, "warm_step_s": step_s,
           "step_group": group, "gc_s": t.spans.within("bench.gc", t_w0, t_w1)}
    t.close()
    del t

    # the plain reference, once the program's state is freed
    t0 = time.perf_counter()
    ref = reference(cell, seed, n_checked)
    values = readings(prog, ref)
    out["correct"], out["checks"] = decide(values, cell.limits)
    where = values.pop("_where")
    log(f"[check] program losses {prog['losses']}, reference "
        f"{ref['losses']}; readings {values}; worst leaves {where}; "
        f"reference took {time.perf_counter() - t0:.1f} s")
    return out
