"""One cell's run: build the server, warm it up, drive the window.

Set-up builds ``AsymCacheServer`` (Pallas attention, wall clock, one
step of pipelining), runs one step of every compiled variant the window
can use, prefills the histories of sessions that began before the window
(their earlier turns, teacher-forced), and serves ``ramp_s`` of traffic.
The window is then ``seconds`` of server clock, driven by
``OnlineFrontend.run()`` over ``AsymCacheServer.serve``.  The server
clock advances by the wall time between step dispatches and jumps over
idle stretches, so a window holds ``seconds`` of busy-or-waiting serving
but no idle wall time.

Sessions arrive open-loop at the traffic file's rate; each session's
next turn is due a think or tool time after its previous turn's last
token.  TTFT is timed from a turn's due time.  Turns due after the
window closes are never issued.  A paced cell then serves until every
turn due in the window has its first token (at most ``drain_s`` more);
a saturated cell stops at the close.  Everything still open is
cancelled.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import math
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench.harness import traffic as tr
from bench.harness import work

BENCH = Path(__file__).resolve().parents[1]
SPANS = ("sched.schedule", "engine.dispatch", "server._retire",
         "source.pop_due")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_module(cfgd: Dict):
    """The configuration's architecture module, ``bench/models/<arch>.py``:
    its ``model_config``, ``shapes``, ``logits``, ``layer_work``,
    ``step_flops`` and ``KERNELS``."""
    return load_module(BENCH / "models" / f"{cfgd['arch']}.py")


def program_block(cfgd: Dict, dry: bool) -> Dict:
    p = dict(cfgd["program"])
    if dry:
        p.update(cfgd["dry"]["program"])
    return p


def engine_config(cfgd: Dict, dry: bool) -> Dict:
    e = dict(cfgd["engine"])
    if dry:
        e.update(cfgd["dry"]["engine"])
    return e


# engine-block keys build_server reads itself; every other key goes to
# EngineConfig unchanged
SERVER_KEYS = ("token_budget", "max_running", "token_buckets", "np_buckets")


def build_server(mcfg, params, e: Dict, impl: str):
    from repro.serving import (AsymCacheServer, EngineConfig,
                               SchedulerConfig, ServerConfig)
    ecfg = EngineConfig(
        attn_impl=impl, token_buckets=tuple(e["token_buckets"]),
        np_buckets=tuple(e["np_buckets"]),
        **{k: v for k, v in e.items() if k not in SERVER_KEYS})
    scfg = ServerConfig(
        num_blocks=e["num_pages"], block_size=e["page_size"], clock="wall",
        pipeline_depth=1, attn_impl=impl,
        scheduler=SchedulerConfig(
            token_budget=e["token_budget"], max_chunk=e["max_chunk"],
            max_prefills=e["max_prefills"], max_decodes=e["max_decodes"],
            max_running=e["max_running"], max_decode_steps=1))
    return AsymCacheServer(mcfg, params, scfg, ecfg=ecfg)


def w_buckets(engine, t_b: int, np_b: int) -> List[int]:
    """Every work-list bucket a step of token bucket ``t_b`` and page
    bucket ``np_b`` can need: prefill chunks pack densely and each spans
    at most ceil(chunk / q_tile) + 1 q tiles, a decode row sits in one
    tile, all-padding tiles add one sentinel item each."""
    from repro.serving.engine import WL_BUCKET
    e = engine.ecfg
    tq = min(e.q_tile, t_b)
    n_tiles = -(-t_b // tq)
    chunk = min(e.max_chunk, t_b)
    bound = (e.max_prefills * (-(-chunk // tq) + 1) + e.max_decodes) \
        * np_b + n_tiles
    bound = min(bound, min(t_b, engine.n_seqs) * np_b * n_tiles + n_tiles)
    out, w = [], WL_BUCKET
    while True:
        out.append(w)
        if w >= bound:
            return out
        w *= 2


def warm_lattice(engine) -> int:
    """Run one no-op step of every variant the window can dispatch (all
    padding: no KV write, no page op), so that each is compiled or
    loaded from the cache before the window."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.msa import WL_FIELDS
    e = engine.ecfg
    n = 0
    for t_b in engine.token_buckets:
        for np_b in engine.np_buckets:
            for w_b in (w_buckets(engine, t_b, np_b)
                        if e.attn_impl != "xla" else [0]):
                layout, size = engine.pack_layout(t_b, np_b, w_b)
                buf = np.zeros((size,), np.int32)
                views = {k: buf[o:o + s] for k, o, s in layout}
                views["swap_k_dst"][:] = e.num_pages
                views["swap_v_dst"][:] = e.num_pages
                if w_b:
                    views["wl_seq"][:] = engine.n_seqs
                    views["wl_init"][0] = 1
                    views["wl_last"][-1] = 1
                inp = {"pack": jnp.asarray(buf), "swap_k": engine._zero_swap,
                       "swap_v": engine._zero_swap}
                ids, _, engine.k_pools, engine.v_pools = engine._step(
                    engine.params, engine.k_pools, engine.v_pools, inp,
                    t_b, np_b, w_b, 1)
                jax.block_until_ready(ids)
                n += 1
    return n


def prefill_histories(server, histories: List[List[int]]) -> int:
    """Teacher-forced prefill of earlier turns: each history is served as
    a prompt of all but its last token with that token as the one output,
    so its KV is what the turns themselves would have left."""
    from repro.serving import Request
    reqs = [Request(rid=-1 - i, session_id=-1 - i, prompt_tokens=h[:-1],
                    output_script=h[-1:], arrival=server.now)
            for i, h in enumerate(histories)]
    if reqs:
        server.run(reqs)
    return sum(len(h) for h in histories)


def scripts_of(plans: List[tr.SessionPlan], t_base: float):
    from repro.serving import SessionScript, TurnScript
    out = []
    for p in plans:
        turns = [TurnScript(output=t.output, tool_result=t.after,
                            is_tool=t.tool_s > 0, tool_duration=t.tool_s,
                            actual_duration=t.wait_s) for t in p.turns]
        out.append(SessionScript(sid=p.sid, arrival=t_base + p.due,
                                 history0=list(p.prompt0), turns=turns))
    return out


@dataclasses.dataclass
class Marks:
    """Counters read at the window's open and close."""
    wall: float = math.nan
    now: float = math.nan
    steps: int = 0
    decode_rows: int = 0
    host_s: float = 0.0
    compiles: int = 0


class Probe:
    """What the window hooks record."""

    def __init__(self, server, clock, t0: float, t_end: float):
        self.server, self.clock = server, clock
        self.t0, self.t_end = t0, t_end
        self.open = Marks()
        self.close = Marks()
        self.token_times: Dict[int, List[float]] = {}
        self.queue: List[tuple] = []
        self.steps = 0
        self.decode_rows = 0
        self.traced_steps: List[tuple] = []
        self.tracing = False
        # longest wall interval between two dispatches in the window, with
        # (prefill tokens, decode rows) of the step dispatched before it,
        # and the window's garbage-collection pauses: where a stall lies
        self.last_dispatch: Optional[tuple] = None
        self.longest_gap = {"s": 0.0, "at_s": None, "prev_step": None}
        self.gc_s, self.gc_max_s, self._gc_t = 0.0, 0.0, None

    def in_window(self) -> bool:
        return self.t0 <= self.server.now < self.t_end

    def on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            d, self._gc_t = time.perf_counter() - self._gc_t, None
            if self.in_window():
                self.gc_s += d
                self.gc_max_s = max(self.gc_max_s, d)

    def mark(self, m: Marks) -> None:
        s = self.server
        m.wall, m.now = time.perf_counter(), s.now
        m.steps, m.host_s = self.steps, s.control_plane_time
        m.decode_rows = self.decode_rows
        m.compiles = self.clock.n

    def on_token(self, req, tok) -> None:
        self.token_times.setdefault(req.rid, []).append(self.server.now)


def plan_summary(plan) -> tuple:
    """(sequences, logit rows) of a step plan for the work counts: each
    sequence's computed positions and context (``work.Seq``)."""
    seqs = [work.seq_of_positions(c.positions) for c in plan.prefills]
    for r in plan.decodes:
        p = r.prompt_len + len(r.generated) - 1
        seqs.append(work.Seq(np.array([p], np.int64), p + 1))
    rows = sum(1 for c in plan.prefills if c.completes_prefill) \
        + len(plan.decodes)
    return seqs, rows


def make_frontend(server, scripts, spec: Dict, probe: Probe,
                  trace: Optional[Dict]):
    """``OnlineFrontend`` that issues no turn due after the window and
    stops the run at the cell's cut-off."""
    import jax
    from repro.serving import FrontendConfig, OnlineFrontend
    from repro.serving.sessions import SessionState

    class WindowFrontend(OnlineFrontend):
        def _push(self, when, kind, sess, turn=-1):
            if when < probe.t_end:
                super()._push(when, kind, sess, turn)

        def pop_due(self, now):
            if trace is not None:
                with jax.profiler.TraceAnnotation("source.pop_due"):
                    self._hooks(now)
                    return super().pop_due(now)
            self._hooks(now)
            return super().pop_due(now)

        def _hooks(self, now):
            sched = self.server.sched
            probe.queue.append((now, len(sched.waiting)
                                + len(sched.running)))
            if math.isnan(probe.open.now) and now >= probe.t0:
                probe.mark(probe.open)
            if trace is not None:
                trace_hook(now, self.server, probe, trace)
            if now >= probe.t_end:
                if math.isnan(probe.close.now):
                    probe.mark(probe.close)
                if self._cut(now):
                    for s in self.sessions:
                        if s.state not in (SessionState.FINISHED,
                                           SessionState.CANCELLED,
                                           SessionState.FAILED):
                            self.cancel_session(s.sid)

        def _cut(self, now):
            if spec["saturated"] or now >= probe.t_end + spec["drain_s"]:
                return True
            return all(not math.isnan(r.first_token_at) or r.terminal
                       for s in self.sessions for r in s.requests
                       if r.arrival < probe.t_end)

    fcfg = FrontendConfig(prefetch=bool(spec["prefetch"]))
    return WindowFrontend(server, scripts, fcfg, on_token=probe.on_token)


def trace_hook(now, server, probe: Probe, trace: Dict) -> None:
    """Start the profiler at ``trace["at"]`` into the window and stop it
    ``trace["len"]`` later, with the device drained at both ends so that
    the traced steps are exactly those dispatched in between.
    ``trace["steps"]`` keeps the steps whose poll phase started or
    stopped the profiler (they drain the device and write the trace)."""
    import jax
    if not trace.get("started") and now >= probe.t0 + trace["at"]:
        jax.block_until_ready(server.engine.k_pools)
        jax.profiler.start_trace(trace["dir"])
        trace["started"] = True
        trace["steps"] = {server.tracer.step}
        trace["span"] = jax.profiler.TraceAnnotation("bench.window")
        trace["span"].__enter__()
        probe.tracing = True
    elif (trace.get("started") and not trace.get("stopped")
          and now >= probe.t0 + trace["at"] + trace["len"]):
        jax.block_until_ready(server.engine.k_pools)
        probe.tracing = False
        trace["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        trace["stopped"] = True
        trace["steps"].add(server.tracer.step)


def instrument(server, probe: Probe, traced: bool) -> None:
    """Count steps, and in a traced run wrap the layer calls in host
    spans and record the plan of every traced step."""
    import jax
    eng = server.engine
    dispatch = eng.dispatch

    def counted(plan):
        probe.steps += 1
        probe.decode_rows += len(plan.decodes)
        if probe.tracing:
            probe.traced_steps.append(plan_summary(plan))
        t = time.perf_counter()
        if probe.last_dispatch is not None and probe.in_window():
            d = t - probe.last_dispatch[0]
            if d > probe.longest_gap["s"]:
                probe.longest_gap = {"s": d, "at_s": server.now - probe.t0,
                                     "prev_step": probe.last_dispatch[1]}
        probe.last_dispatch = (t, (sum(len(c.positions)
                                       for c in plan.prefills),
                                   len(plan.decodes)))
        return dispatch(plan)

    gc.callbacks.append(probe.on_gc)
    if not traced:
        eng.dispatch = counted
        return

    def span(name, fn):
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    eng.dispatch = span("engine.dispatch", counted)
    server.sched.schedule = span("sched.schedule", server.sched.schedule)
    server._retire = span("server._retire", server._retire)


def program_record(tracer, t0: float, t_end: float, profiler_steps=()
                   ) -> Optional[Dict]:
    """The window's steps from the server tracer's ring (dispatched at
    server clock ``t0 <= now < t_end``), less ``profiler_steps``: the
    steps whose poll phase started or stopped the profiler.  Steps, step
    variants compiled, seconds per serve-loop phase, the work-lists'
    items and pages, and the longest dispatch-to-dispatch interval with
    the phases that filled it.  None when the window holds no step."""
    from repro.serving.tracing import PHASES, POSTPROCESS
    ring = tracer.steps()
    recs = [r for r in ring if t0 <= r.now < t_end
            and r.step not in profiler_steps]
    if not recs:
        return None
    phase_s = {p: sum(r.phases[i] for r in recs)
               for i, p in enumerate(PHASES)}
    # an interval runs from the previous launch's end to this one's: the
    # previous step's postprocess, wait and retire, then this step's poll,
    # schedule, assemble and launch
    prev = {r.step: r for r in ring}
    longest = max(recs, key=lambda r: r.interval_s)
    before = prev.get(longest.step - 1)
    filled = {p: (before.phases[i] if before is not None else 0.0)
              if i >= POSTPROCESS else longest.phases[i]
              for i, p in enumerate(PHASES)}
    return {
        "steps": len(recs),
        "compiles": sum(1 for r in recs if r.compiled),
        "phase_s": phase_s,
        "worklist_s": sum(r.worklist_s for r in recs),
        "host_loop_s": sum(v for p, v in phase_s.items() if p != "wait"),
        "interval_s": sum(r.interval_s for r in recs),
        "wl_items": sum(r.wl_items for r in recs),
        "wl_pages": sum(r.wl_pages for r in recs),
        "longest_step": dict(longest.as_dict(), interval_phases=filled),
        "intervals_over_1_5_s": sum(1 for r in recs if r.interval_s > 1.5),
        "profiler_steps": sorted(profiler_steps),
    }


def window_turns(frontend, probe: Probe) -> List:
    return [r for s in frontend.sessions for r in s.requests
            if probe.t0 <= r.arrival < probe.t_end]


def recompute_counts(frontend, block: int, warm_ctx: Dict[int, int]):
    """(recomputed, prompt positions, multi-segment) per served turn, by
    rid.  A turn's prompt begins with its session's earlier context,
    whose full blocks were computed and committed before; computing one
    of those positions again recomputes an evicted block.  A compute list
    in several runs, or starting inside the committed region, means the
    context was served as several cached and recomputed segments."""
    out = {}
    for s in frontend.sessions:
        before = warm_ctx.get(s.sid, 0)
        for r in s.requests:
            cl = np.asarray(r.compute_list)
            done = (before - 1) // block * block if before else 0
            rec = int(np.sum(cl < done))
            gaps = int(np.sum(np.diff(cl) > 1)) if cl.size else 0
            multi = bool(cl.size and (gaps or 0 < cl[0] < done))
            out[r.rid] = (rec, r.prompt_len, multi)
            before = r.prompt_len + len(r.output_script)
    return out


def release(server) -> None:
    """Free the program's device state (pools, executables, buffers)."""
    eng = server.engine
    for name in ("k_pools", "v_pools"):
        arr = getattr(eng, name, None)
        if arr is not None:
            arr.delete()
    gc.collect()
