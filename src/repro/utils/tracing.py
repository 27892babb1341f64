"""Host spans and work counters of the association request, off by default.

With tracing on, the association code writes ``hfel.*`` spans and a
``hfel.count`` event of work counters into the JAX profiler's trace. They
land on the ``/host:CPU`` plane, which shares one clock with the device
planes, so a trace shows what the host was doing around every device op::

    from repro.utils import tracing

    tracing.enable(True)
    jax.profiler.start_trace(log_dir)
    ...                                   # engine build, run, finalize
    jax.profiler.stop_trace()

On, each ``span`` also keeps its host duration when it closes; a caller
that times requests takes them, and clears them, with
:func:`take_durations` (one call per request).

Off (the default), :func:`span` returns one shared no-op context,
:func:`ready` returns its argument untouched and :func:`event` does
nothing, so the hot path pays neither a sync, an annotation nor a clock
read. Spans and
events belong in host code only: inside a jitted function they would fire
once, at trace time. Device code carries ``jax.named_scope`` names
instead, which cost nothing at run time.
"""

from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext

import jax

_ON = False
_OFF = nullcontext()
# host seconds of each closed span since the last take_durations(), by name
_DURATIONS: dict[str, list[float]] = {}


def enable(on: bool) -> None:
    """Turn the spans, the device waits and the counter events on or off."""
    global _ON
    _ON = bool(on)


@contextmanager
def _timed(name: str):
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    _DURATIONS.setdefault(name, []).append(time.perf_counter() - t0)


def span(name: str):
    """A host span named ``name`` in the profiler's trace, whose duration
    is kept for :func:`take_durations`."""
    return _timed(name) if _ON else _OFF


def take_durations() -> dict[str, list[float]]:
    """The host seconds of every span closed since the last call, by span
    name in closing order; clears them. Empty while tracing is off."""
    global _DURATIONS
    out, _DURATIONS = _DURATIONS, {}
    return out


def ready(x):
    """``x``, waited for on the device when tracing is on, so that a span
    closing on it measures the device phase and not its dispatch."""
    return jax.block_until_ready(x) if _ON else x


def event(name: str, **counts) -> None:
    """A zero-length event whose stats are ``counts``."""
    if _ON:
        with jax.profiler.TraceAnnotation(name, **counts):
            pass
