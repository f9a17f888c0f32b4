"""Host spans and blocking-fetch accounting on the serving and control path.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``:
while the profiler records, it lands on the profiler's clock beside the
device's events; while it does not, entering one costs about a microsecond.
Spans mark the phases of a tick (``plane.*``, ``fe.*``), never a jitted
function's body, a request or a slot.

``timed_get`` is the one blocking device-to-host fetch: it runs inside a
``repro.fetch`` span and is accounted on its ``owner`` (an engine, a fleet
group or the control plane), which bumps ``owner.syncs`` and adds the
blocked wall seconds to ``owner.sync_wait``.
"""
from __future__ import annotations

import time

import jax

PREFIX = "repro."


def span(name: str) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name)


def timed_get(owner, arrays):
    """``jax.device_get(arrays)``, its blocked time accounted on ``owner``."""
    with span("fetch"):
        t0 = time.perf_counter()
        out = jax.device_get(arrays)
        owner.sync_wait += time.perf_counter() - t0
    owner.syncs += 1
    return out
