"""Drive one measured window through ``ServingEngine.run``.

The probe wraps methods on the engine instance (and on its scheduler,
pool, tierer and metrics), as one would wrap a library's hooks; the
program's code is unchanged.  Three wrappers run in every window:

- ``sched.admit`` opens each iteration.  Once the window's time is up
  it ends the run there, between iterations, by raising
  :class:`WindowClosed` out of ``engine.run``.
- ``sched.finish`` closes the loop: the client whose request finished
  sends its next one at once.
- ``metrics.on_token`` timestamps every token at hand-over, on the
  engine's clock (the step's argmax read-back has synced it).

With ``trace`` on, the probe also times the calls into each layer and
marks them with ``jax.profiler.TraceAnnotation`` spans named
``bench.<what>``, counts the KV bytes that cross between host and
device, and records a profiler trace from the iteration in which the
batch is first full to the window's close.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

FAST_KIND = "device"


class WindowClosed(Exception):
    """Raised out of ``engine.run`` when the window's time is up."""


@dataclasses.dataclass
class RequestRecord:
    rid: int
    client: int
    prompt: np.ndarray
    max_new_tokens: int
    t_send: float
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_finish: Optional[float] = None


@dataclasses.dataclass
class DecodeStep:
    t_start: float
    lengths: np.ndarray        # cached tokens per batch row; 0 = pad row
    gather_s: float            # host time from the first gather to here


class Span:
    """A profiler span that can open in one call and close in another."""

    def __init__(self, name: str):
        import jax
        self._ann = jax.profiler.TraceAnnotation(name)
        self._ann.__enter__()

    def close(self) -> None:
        self._ann.__exit__(None, None, None)


class Probe:
    def __init__(self, engine, loop, seconds: float, trace_dir=None,
                 clock: Callable[[], float] = time.perf_counter):
        self.e = engine
        self.loop = loop
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.clock = clock
        self.records: Dict[int, RequestRecord] = {}
        self._reqs: Dict[int, object] = {}
        self.refused = 0
        self.compiles = 0
        self.prefills: List[tuple] = []        # (t_start, seconds, tokens)
        self.steps: List[DecodeStep] = []
        self.host_kv_bytes = 0                 # gathers and appends
        self.migrated_bytes = 0
        self.preemptions = 0
        self.iter_starts: List[float] = []     # every iteration's admit
        self.traced_steps = (0, 0)             # steps[a:b] were traced
        self.trace_window = None               # (t_start, t_stop)
        self.t_open = self.t_close = None
        self._open = False
        self._tracing = False
        self._gather_t = None
        self._gather_span = None
        self._window_span = None
        self._undo: List[tuple] = []

    # ------------------------------------------------------------------
    def on_compile(self, event: str, *_a, **_k) -> None:
        """A ``jax.monitoring`` listener: one XLA program obtained."""
        if self._open and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _wrap(self, obj, name: str, make) -> None:
        orig = getattr(obj, name)
        self._undo.append((obj, name, vars(obj).get(name)))
        setattr(obj, name, make(orig))

    def detach(self) -> None:
        """Remove every wrapper and drop the engine."""
        for obj, name, own in reversed(self._undo):
            if own is None:
                delattr(obj, name)
            else:
                setattr(obj, name, own)
        self._undo.clear()
        self.e = None
        self._reqs.clear()

    # ------------------------------------------------------------------
    def _send(self, client: int) -> None:
        """``client`` sends its next request (refusals count as failed
        and the client moves on)."""
        for _ in range(8):
            spec, prompt = self.loop.next_request(client)
            t = self.clock()
            try:
                rid = self.e.submit(prompt, spec.max_new_tokens)
            except ValueError:
                self.refused += 1
                continue
            self._reqs[rid] = self.e.sched.waiting[-1]
            self.records[rid] = RequestRecord(rid, client, prompt,
                                              spec.max_new_tokens, t)
            return
        raise RuntimeError(f"client {client}: eight requests in a row "
                           f"were refused")

    def _install(self) -> None:
        e, sched = self.e, self.e.sched

        def admit(orig):
            def f(now_s=0.0):
                t = self.clock()
                if t >= self.t_end:
                    self._close(t)
                    raise WindowClosed()
                self.iter_starts.append(t)
                if (self.trace_dir is not None and not self._tracing
                        and self.trace_window is None
                        and (len(sched.running) >= e.max_batch
                             or t >= self.t_open + self.seconds / 3)):
                    self._start_trace(t)
                return orig(now_s)
            return f

        def finish(orig):
            def f(req):
                orig(req)
                rec = self.records.get(req.rid)
                if rec is not None:
                    rec.t_finish = self.clock()
                    if self.clock() < self.t_end:
                        self._send(rec.client)
            return f

        def on_token(orig):
            def f(rid, t):
                orig(rid, t)
                rec = self.records.get(rid)
                if rec is not None:
                    rec.times.append(t + e._t0 - e._virtual_skew)
            return f

        def on_preempt(orig):
            def f(rid, t):
                orig(rid, t)
                self.preemptions += 1
            return f

        self._wrap(sched, "admit", admit)
        self._wrap(sched, "finish", finish)
        self._wrap(e.metrics, "on_token", on_token)
        self._wrap(e.metrics, "on_preempt", on_preempt)
        if self.trace_dir is not None:
            self._install_spans()

    def _install_spans(self) -> None:
        import jax
        e, pool = self.e, self.e.pool
        ann = jax.profiler.TraceAnnotation
        bn = pool.block_nbytes()

        def spanned(name):
            def make(orig):
                def f(*a, **k):
                    with ann(name):
                        return orig(*a, **k)
                return f
            return make

        def prefill(orig):
            def f(req, now):
                n = len(req.prefill_tokens())
                t = self.clock()
                with ann("bench.prefill"):
                    orig(req, now)
                self.prefills.append((t, self.clock() - t, n))
            return f

        def gather(orig):
            def f(seq_id, pad_blocks):
                if self._gather_t is None:
                    self._gather_t = self.clock()
                    self._gather_span = Span("bench.kv_gather")
                self.host_kv_bytes += bn * sum(
                    1 for b in pool.seq_blocks(seq_id)
                    if b.k is not None and b.kind != FAST_KIND)
                return orig(seq_id, pad_blocks)
            return f

        def decode(orig):
            def f(params, tokens, kv_k, kv_v, lengths):
                t = self.clock()
                gather_s = 0.0
                if self._gather_t is not None:
                    gather_s = t - self._gather_t
                    self._gather_span.close()
                    self._gather_t = self._gather_span = None
                with ann("bench.decode"):
                    out = orig(params, tokens, kv_k, kv_v, lengths)
                self.steps.append(DecodeStep(t, np.array(lengths),
                                             gather_s))
                return out
            return f

        def append(orig):
            def f(seq_id, k_tok, v_tok):
                n = pool.seq_len[seq_id]
                b = pool.blocks[pool.table[seq_id][n // pool.block_tokens]]
                if b.kind != FAST_KIND:
                    # a written block goes to the device and back; a
                    # fresh one is made on the device and sent out
                    self.host_kv_bytes += bn * (2 if b.k is not None
                                                else 1)
                with ann("bench.append"):
                    return orig(seq_id, k_tok, v_tok)
            return f

        self._wrap(e, "_do_prefill", prefill)
        self._wrap(pool, "gather_seq", gather)
        self._wrap(e, "_decode", decode)
        self._wrap(pool, "append_token", append)
        self._wrap(e.tierer, "step", spanned("bench.tierer"))
        self._wrap(e, "_replan_step", spanned("bench.replan"))

    # ------------------------------------------------------------------
    def _start_trace(self, t: float) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # spans only, no call tracing
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=opts)
        self._tracing = True
        self._window_span = Span("bench.window")
        self.trace_window = (self.clock(), None)
        self._trace_step0 = len(self.steps)

    def _stop_trace(self) -> None:
        import jax
        self._window_span.close()
        self.trace_window = (self.trace_window[0], self.clock())
        self.traced_steps = (self._trace_step0, len(self.steps))
        jax.profiler.stop_trace()
        self._tracing = False

    def _close(self, t: float) -> None:
        self.t_close = t
        self._open = False
        if self._tracing:
            self._stop_trace()
        self.migrated_bytes = (self.e.pool.counters.migrated_bytes
                               - self._migrated0)
        for rid, req in self._reqs.items():
            rec = self.records[rid]
            rec.tokens = list(req.out_tokens[:len(rec.times)])

    def run(self) -> None:
        """Open the window, send every client's first request, serve
        until the window's time is up, and close it."""
        self._install()
        self._migrated0 = self.e.pool.counters.migrated_bytes
        self.t_open = self.clock()
        self.t_end = self.t_open + self.seconds
        self._open = True
        for c in range(self.loop.mix.clients):
            self._send(c)
        try:
            self.e.run(max_iterations=1 << 62)
        except WindowClosed:
            pass
        else:
            raise RuntimeError("the engine ran dry before the window "
                               "closed; the closed loop lost a client")
