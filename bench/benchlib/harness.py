"""What every traffic kind shares: the measured window, its compile count,
the host spans the trace reduction names gaps by, and the run's outcome.

A kind (``kinds/<kind>.py``) exposes ``drive(cell, seed, seconds,
trace_dir, devs, t_start) -> Outcome``: it builds its inputs from the seed,
warms every shape it uses, measures whole calls that end with the result
ready for ``seconds``, reads the chip's memory peak, frees the program's
state, and only then runs the plain reference over what the window made.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
from typing import Any

import jax

from benchlib.trace import WINDOW_SPAN

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass
class Outcome:
    setup_s: float
    metrics: dict[str, float]      # end-to-end values of the window
    attempted: int
    failed: int
    numbers: dict[str, float]      # what the comparison reads
    view: dict[str, Any]           # what the per-layer readers need
    device: dict[str, Any]
    hlo_texts: tuple[str, ...] = ()


class CompileCounter:
    """Counts lowerings and backend compiles while open."""

    def __init__(self):
        self.count = 0
        self._on = False

    def _listen(self, event, duration, **kwargs):
        if self._on and event in COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


@contextlib.contextmanager
def window(trace_dir: str | None):
    """The measured window: profiled when ``trace_dir`` is given, always
    marked by the ``bench.window`` host span."""
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        if trace_dir:
            jax.profiler.stop_trace()


def span(name: str):
    return jax.profiler.TraceAnnotation("bench." + name)


def free():
    gc.collect()
    jax.clear_caches()


def hlo_text(jitted, *args, **kwargs) -> str:
    """The compiled program the window drove (a persistent-cache hit), for
    the trace's op-to-phase join."""
    return jitted.lower(*args, **kwargs).compile().as_text()
