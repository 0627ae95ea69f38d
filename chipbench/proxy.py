"""The benchmark's spans around the engine, as the serving loop sees it.

``ServingLoop`` is engine-agnostic: it reads ``bucket_sizes``,
``metrics`` and ``_clock`` and calls ``run_plan(plan, sync=False)``.
``EngineProxy`` forwards those to the real engine and records, for each
plan, the host-clock span of the dispatch call, its valid rows, its
bucket, the request ids it carries and when its logits reached the
host.  In a traced run each call is also a ``TraceAnnotation``, so the
device's idle gaps can be laid against what the host was doing.
"""

from __future__ import annotations

import contextlib
import time

DISPATCH = "chipbench.dispatch"
REALIZE = "chipbench.realize"

# plan record fields
T_DISPATCH, T_DISPATCHED, N_VALID, BUCKET, RIDS, T_REALIZED = range(6)


def annotator(trace: bool):
    if trace:
        import jax
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


class EngineProxy:
    def __init__(self, engine, *, trace: bool = False,
                 clock=time.perf_counter):
        self.engine = engine
        self.bucket_sizes = engine.bucket_sizes
        self.metrics = engine.metrics
        self._clock = engine._clock
        self._now = clock
        self._annotate = annotator(trace)
        self.plans: list[list] = []
        self.realized_jets = 0
        #: request ids of each plan as it is realized, for the load
        #: generator to collect finished requests (it empties the list)
        self.realized_rids: list[tuple] = []

    def run_plan(self, plan, *, sync: bool = True):
        t0 = self._now()
        with self._annotate(DISPATCH):
            handle = self.engine.run_plan(plan, sync=False)
        rec = [t0, self._now(), plan.n_valid, plan.bucket,
               tuple(r for r, _, _ in plan.requests), None]
        self.plans.append(rec)
        timed = _TimedHandle(self, handle, rec)
        return timed.result() if sync else timed


class _TimedHandle:
    __slots__ = ("_proxy", "_handle", "_rec")

    def __init__(self, proxy: EngineProxy, handle, rec: list):
        self._proxy, self._handle, self._rec = proxy, handle, rec

    @property
    def ready(self) -> bool:
        return self._handle.ready

    def result(self):
        with self._proxy._annotate(REALIZE):
            out = self._handle.result()
        if self._rec[T_REALIZED] is None:
            self._rec[T_REALIZED] = self._proxy._now()
            self._proxy.realized_jets += self._rec[N_VALID]
            self._proxy.realized_rids.append(self._rec[RIDS])
        return out
