"""Host spans around the engine's layers, for the traced run only.

Each wrapper is set on an instance (the engine, its admission policy, its
workers) and writes a ``jax.profiler.TraceAnnotation`` named ``bench.<layer
call>``, so the spans land in the profiler's trace on the device trace's
clock. The per-layer metrics in ``bench/metrics`` read them by these names.
"""
from __future__ import annotations

import jax

ENGINE = ("_serve_round", "_admit", "_plan_for", "_prefill_plan_for", "_drift_event")
WORKER = ("prefill_batch", "write_slots", "decode_pool")


def _wrap(obj, attr: str, name: str) -> None:
    fn = getattr(obj, attr)

    def spanned(*a, **k):
        with jax.profiler.TraceAnnotation("bench." + name):
            return fn(*a, **k)

    setattr(obj, attr, spanned)


def install(eng) -> None:
    for attr in ENGINE:
        _wrap(eng, attr, attr)
    _wrap(eng.admission, "decide", "admission.decide")
    for w in eng.workers.values():
        for attr in WORKER:
            _wrap(w, attr, attr)
