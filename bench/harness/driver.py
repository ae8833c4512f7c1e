"""Set-up and the measured window: drives the engine as a client would.

Every iteration of the window is ``engine.run(max_ticks=1)``: one admission
step (a prefill chunk or a zero-prefill adoption) and one decode tick of
every active slot, then ``engine.submit`` of each client request that
finished, sent again.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import jax

from harness import program


@dataclass
class TickLog:
    """Wraps the engine's decode tick to note, for every tick, when it was
    dispatched and the context each active slot attends over (its length
    after the tick's append). Changes nothing the tick computes."""
    engine: object
    ticks: list = field(default_factory=list)       # [(t, [contexts])]

    def __post_init__(self):
        self.fn = self.engine._decode
        self.engine._decode = self

    def __call__(self, params, state, tokens, active):
        e = self.engine
        self.ticks.append((time.time(), [e._slot_pos[s] + 1 for s in e._active
                                         if e._mask[s]]))
        return self.fn(params, state, tokens, active)


class CompileCounter:
    """Counts executables built (compiled or read from the persistent cache)
    after `arm`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.armed = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self.armed and event == self.EVENT:
            self.count += 1

    def arm(self):
        self.count, self.armed = 0, True

    def disarm(self) -> int:
        self.armed = False
        return self.count


class GcClock:
    """Times the interpreter's garbage collections between `arm` and
    `disarm` (a stall the host clock would put into a token gap)."""

    def __init__(self):
        self.pauses, self.armed, self._t = [], False, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if not self.armed:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))
            self._t = None

    def arm(self):
        self.pauses, self.armed = [], True

    def disarm(self) -> list:
        self.armed = False
        gc.callbacks.remove(self._on_gc)
        return self.pauses


def run_until_idle(engine, limit_s: float = 600.0) -> None:
    t_end = time.time() + limit_s
    while program.busy(engine):
        if time.time() > t_end:
            raise RuntimeError("set-up: the engine did not drain")
        engine.run(max_ticks=1)


def setup_closed(engine, items, mix) -> list:
    """Builds each client's context and warms every program the window
    runs. First each context is prefilled once (one token), which leaves its
    blocks in the prefix cache; then the client's long request is sent and
    admitted by zero-prefill adoption of those blocks, as a resent request
    is in the window; then ``warm_ticks`` decode ticks run."""
    for it in items:
        req = program.make_request(it, time.time())
        req.rid, req.max_new_tokens = -1 - it.rid, 1
        engine.submit(req)
        run_until_idle(engine)
    live = []
    for it in items:
        req = program.make_request(it, time.time())
        engine.submit(req)
        live.append(req)
    while engine._queue or engine._inflight is not None:
        engine.run(max_ticks=1)
    for _ in range(mix["warm_ticks"]):
        engine.run(max_ticks=1)
    return live


@dataclass
class Window:
    t0: float
    t1: float
    requests: list              # every request the window served
    trace_span: tuple | None    # (t0, t1) host clock of the traced part


def window_closed(engine, live, seconds: float, tracer=None) -> Window:
    """Each client keeps one request in the engine: a finished one is sent
    again (the prefix cache then adopts its context without prefill)."""
    requests = list(live)
    t0 = time.time()
    end = t0 + seconds
    if tracer is not None:
        tracer.start()
    while time.time() < end:
        engine.run(max_ticks=1)
        if tracer is not None:
            tracer.poll()
        for i, req in enumerate(live):
            if req.done_time is not None:
                again = program.resend(req, max(r.rid for r in requests) + 1,
                                       time.time())
                engine.submit(again)
                live[i] = again
                requests.append(again)
    t1 = time.time()
    if tracer is not None:
        tracer.stop()
    return Window(t0, t1, requests, tracer.span if tracer else None)


class Tracer:
    """Profiles the first ``seconds`` of the window into ``logdir``; the
    traced span is annotated so the reduction finds it on the trace's own
    clock."""

    NAME = "bench.traced_window"

    def __init__(self, logdir: str, seconds: float):
        self.logdir, self.seconds = logdir, seconds
        self.span = None
        self._ann = None

    def start(self):
        jax.profiler.start_trace(self.logdir)
        self._ann = jax.profiler.TraceAnnotation(self.NAME)
        self._ann.__enter__()
        self._t0 = time.time()

    def poll(self):
        if self._ann is not None and time.time() - self._t0 >= self.seconds:
            self._close()

    def _close(self):
        self._ann.__exit__(None, None, None)
        self._ann = None
        self.span = (self._t0, time.time())
        jax.profiler.stop_trace()

    def stop(self):
        if self._ann is not None:
            self._close()
