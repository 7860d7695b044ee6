"""Host phases of the serve loop: profiler spans and counters from one clock.

Every iteration of :meth:`AsymCacheServer.serve` that dispatches a step
runs under ``jax.profiler.StepTraceAnnotation("serve.step", step_num=n)``,
and its host work is cut into phases that tile the loop end to end:

==================  ===================================================
``serve.poll``      the source poll, admission, pre-flight, deadline
                    sweep and pin expiry
``serve.schedule``  ``sched.schedule``, the copy-on-write drain, and any
                    empty-plan iterations before the step
``serve.assemble``  ``Engine.build_inputs``; its child span
                    ``serve.assemble.worklist`` covers the work-list
                    build
``serve.launch``    the jitted step call (the enqueue only)
``serve.postprocess``  ``_postprocess``
``serve.wait``      blocking until the retired step's outputs are ready
``serve.retire``    the fetch and bookkeeping of ``_retire``
==================  ===================================================

Each phase is a ``jax.profiler.TraceAnnotation`` with a ``step=<n>``
argument (and ``compiled=1`` on the launch that traced a new step
variant), so a profiler trace shows which step each span served.  One
``time.perf_counter`` read marks each boundary; the same reads feed the
cumulative phase counters, the per-step ring and the server's wall
clock.  With no profiler running a span costs about a microsecond, so
the tracer is always on.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax

PHASES = ("poll", "schedule", "assemble", "launch", "postprocess", "wait",
          "retire")
POLL, SCHEDULE, ASSEMBLE, LAUNCH, POSTPROCESS, WAIT, RETIRE = range(7)
SPAN_NAMES = tuple(f"serve.{p}" for p in PHASES)
WORKLIST_SPAN = "serve.assemble.worklist"
# per-step records kept (the oldest fall out)
RING_STEPS = 8192


class StepRecord(NamedTuple):
    step: int
    now: float              # server clock when the step was dispatched
    interval_s: float       # wall seconds since the previous dispatch
    phases: Tuple[float, ...]   # seconds of each of PHASES, in order
    worklist_s: float       # part of the assemble phase building work-lists
    prefill_tokens: int
    decode_rows: int
    variant: Tuple[int, ...]    # the step's jit variant, token bucket first
    compiled: bool          # the launch traced a new step variant
    # the step's work-lists (Pallas attention only, else 0): page-block
    # items and the pages they cover, before padding; the block fill is
    # wl_pages / (wl_items * block_pages(page)); wl_narrow_items of the
    # items belong to one-token runs (decode rows), which the kernel
    # computes on their token's query rows alone
    wl_items: int = 0
    wl_pages: int = 0
    wl_narrow_items: int = 0

    @property
    def t_bucket(self) -> int:
        return self.variant[0] if self.variant else 0

    def as_dict(self) -> Dict:
        d = self._asdict()
        d["phases"] = dict(zip(PHASES, self.phases))
        d["t_bucket"] = self.t_bucket
        return d


class ServeTracer:
    """Phase spans, counters and per-step records of the serve loop.

    The loop calls :meth:`start` once, :meth:`phase` at every boundary
    and :meth:`stop` at its end; the engine marks the assemble and
    launch phases and reports each launch through :meth:`launched`.  A
    step's record is committed when the next step's poll begins (or at
    :meth:`stop`), so it holds the whole iteration that dispatched it,
    the empty-plan iterations before it included."""

    def __init__(self):
        n = len(PHASES)
        self.totals = [0.0] * n          # cumulative seconds per phase
        self.calls = [0] * n             # times each phase was entered
        self.worklist_s = 0.0
        self.worklist_calls = 0
        self.ring: deque = deque(maxlen=RING_STEPS)
        self.n_steps = 0                 # steps dispatched so far
        self.step = 0                    # step the loop is working towards
        self.opened = [0.0] * n          # last time each phase opened
        self.t = 0.0                     # last boundary
        self.t_dispatch = 0.0            # last launch's end
        self.interval = 0.0              # between the last two launches
        self._cur: Optional[int] = None
        self._span = None
        self._step_span = None
        self._acc = [0.0] * n
        self._wl = 0.0
        self._wl_work = (0, 0, 0)   # (items, pages, narrow) of this step
        self._info: Optional[tuple] = None   # (now, prefill, decode rows)
        self._launch: Tuple[tuple, bool] = ((), False)

    # ---- the serve loop ------------------------------------------------
    def start(self) -> float:
        """Begin the first step; returns the clock read that the first
        phase is counted from.  What a loop that raised left open is
        dropped uncounted."""
        if self._cur is not None:
            self._span.__exit__(None, None, None)
            self._cur = None
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
        self._acc = [0.0] * len(PHASES)
        self._wl, self._wl_work, self._info = 0.0, (0, 0, 0), None
        t = time.perf_counter()
        self.t = self.t_dispatch = t
        self._begin_step()
        return t

    def phase(self, i: int) -> float:
        """Close the open phase and open phase ``i`` (the first phase
        after :meth:`start` is counted from it); a poll after a dispatch
        also ends that step and begins the next.  Returns the one clock
        read of the boundary."""
        t = time.perf_counter()
        self._close(t)
        if i == POLL and self._info is not None:
            self._end_step()
            self._begin_step()
        self._open(i, t)
        return t

    def stop(self) -> float:
        """Close the open phase and, if it dispatched, the last step."""
        t = time.perf_counter()
        self._close(t)
        if self._info is not None:
            self._end_step()
        else:
            self._step_span.__exit__(None, None, None)
            self._step_span = None
        self._cur = None
        return t

    def dispatched(self, now: float, prefill_tokens: int,
                   decode_rows: int) -> None:
        """The step was dispatched at server clock ``now``."""
        self._info = (now, prefill_tokens, decode_rows)

    # ---- the engine ------------------------------------------------------
    def launched(self, variant: Tuple[int, ...], compiled: bool) -> None:
        """The launch phase's jitted call ran step variant ``variant``
        (its static bucket arguments, token bucket first) and, with
        ``compiled``, traced it anew."""
        self._launch = (variant, compiled)
        if compiled and self._span is not None:
            self._span.set_metadata(compiled=1)

    def worklist_built(self, items: int, pages: int, narrow: int) -> None:
        """A work-list of this step holds ``items`` page-block items
        covering ``pages`` pages (before padding), ``narrow`` of them
        items of one-token runs."""
        self._wl_work = tuple(
            a + b for a, b in zip(self._wl_work, (items, pages, narrow)))

    @contextlib.contextmanager
    def worklist(self):
        """``serve.assemble.worklist``: the work-list build inside
        assemble, a child span timed by its own pair of clock reads."""
        with jax.profiler.TraceAnnotation(WORKLIST_SPAN, step=self.step):
            t = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t
                self.worklist_s += dt
                self.worklist_calls += 1
                self._wl += dt

    # ---- readers ---------------------------------------------------------
    def phase_totals(self) -> Dict[str, Dict[str, float]]:
        out = {p: {"s": self.totals[i], "calls": self.calls[i]}
               for i, p in enumerate(PHASES)}
        out["assemble.worklist"] = {"s": self.worklist_s,
                                    "calls": self.worklist_calls}
        return out

    def steps(self, lo: float = float("-inf"),
              hi: float = float("inf")) -> List[StepRecord]:
        """Kept records of the steps dispatched at server clock
        ``lo <= now < hi``."""
        return [r for r in self.ring if lo <= r.now < hi]

    # ---- internals -------------------------------------------------------
    def _open(self, i: int, t: float) -> None:
        self._cur = i
        self.calls[i] += 1
        self.opened[i] = t
        self._span = jax.profiler.TraceAnnotation(SPAN_NAMES[i],
                                                  step=self.step)
        self._span.__enter__()

    def _close(self, t: float) -> None:
        i = self._cur
        if i is None:
            return
        dt = t - self.t
        self.totals[i] += dt
        self._acc[i] += dt
        self.t = t
        if i == LAUNCH:
            self.interval = t - self.t_dispatch
            self.t_dispatch = t
        self._span.__exit__(None, None, None)
        self._span = None

    def _begin_step(self) -> None:
        self.step = self.n_steps + 1
        self._step_span = jax.profiler.StepTraceAnnotation(
            "serve.step", step_num=self.step)
        self._step_span.__enter__()

    def _end_step(self) -> None:
        now, pre, dec = self._info
        self.ring.append(StepRecord(
            self.step, now, self.interval, tuple(self._acc), self._wl,
            pre, dec, *self._launch, *self._wl_work))
        self.n_steps += 1
        self._acc = [0.0] * len(PHASES)
        self._wl = 0.0
        self._wl_work = (0, 0, 0)
        self._info = None
        self._launch = ((), False)
        self._step_span.__exit__(None, None, None)
        self._step_span = None

