"""The round program's own spans and counters, off by default.

    from repro import obs
    obs.enable()
    run_federated(...)
    obs.snapshot()   # {"spans": {name: [count, seconds]}, "counters": {...}}

When on:

- ``span(name, **meta)`` opens a ``jax.profiler.TraceAnnotation`` (so a
  profiler trace shows it on the device ops' clock, with ``meta`` as its
  stats) and adds its host seconds and one call to the registry;
- ``count(name, n)`` adds ``n`` to a counter;
- ``to_host(x)`` is the round path's one way of reading a device value
  back to the host, and counts each call under ``host_syncs``;
- seconds JAX spends tracing, lowering and compiling are added under
  ``compile_s.<innermost open span>`` (``compile_s.other`` outside any).

When off, ``span`` returns one shared no-op context and nothing is
recorded: the cost is one boolean test per call.  Every span name starts
with ``fl.``.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List

import jax
import numpy as np

# jax.monitoring duration events: tracing, lowering and backend compiles
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")

_NULL = contextlib.nullcontext()
_on = False
_listening = False
_spans: Dict[str, List] = {}        # name -> [count, seconds]
_counters: Dict[str, float] = {}
_open: List[str] = []               # names of the open spans, innermost last
_lock = threading.Lock()            # compiles may report from other threads


class _Span:
    __slots__ = ("name", "annotation", "t0")

    def __init__(self, name: str, meta: Dict):
        self.name = name
        self.annotation = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self):
        self.annotation.__enter__()
        _open.append(self.name)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self.t0
        _open.pop()
        rec = _spans.setdefault(self.name, [0, 0.0])
        rec[0] += 1
        rec[1] += seconds
        self.annotation.__exit__(*exc)
        return False


def span(name: str, **meta):
    """A timed, annotated region (a no-op context when off)."""
    if not _on:
        return _NULL
    return _Span(name, meta)


def _add(name: str, n) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def count(name: str, n=1) -> None:
    if _on:
        _add(name, n)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``: a blocking read of a device value, counted
    under ``host_syncs``."""
    if _on:
        _add("host_syncs", 1)
    return np.asarray(x)


def _compile_seconds(event: str, duration: float, **_) -> None:
    if _on and event in COMPILE_EVENTS:
        _add("compile_s." + (_open[-1:] or ["other"])[0], duration)


def enable() -> None:
    global _on, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(
            _compile_seconds)
        _listening = True
    _on = True


def disable() -> None:
    global _on
    _on = False


def reset() -> None:
    with _lock:
        _spans.clear()
        _counters.clear()


def snapshot() -> Dict:
    """A copy of the registry: ``{"spans": {name: [count, seconds]},
    "counters": {name: value}}``."""
    with _lock:
        return {"spans": {k: list(v) for k, v in _spans.items()},
                "counters": dict(_counters)}
