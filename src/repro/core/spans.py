"""Named host spans and jit counters: the program's only tracing code.

``span(name)`` names a stretch of host work ``repro.<name>`` in a
``jax.profiler`` trace (``spanned(name)`` names every call of a
function).  It is a ``TraceAnnotation``: inert (a fraction of
a microsecond) when no profiler session is active, so it needs no switch.
The spans land on the profiler's host plane, on the same clock as the
device planes, so every stretch in which the device idles can be put down
to the program's own layer boundaries:

  repro.sweep          run_sweep, batched_congruence
  repro.shard_sweep    one shard_sweep; repro.shard each of its shards
  repro.popgen         population generation (a shard, a gather, a batch)
  repro.stage          stacking, padding and the host->device copy
  repro.fetch          every device->host copy of a kernel result
  repro.reduce         host reductions (best fits, means, shard merges)
  repro.pareto         the 2-D and 3-D Pareto filters
  repro.rescore        shard_sweep's re-score of the survivors
  repro.codesign       one grad_codesign solve
  repro.descent.step   one compiled descent loop, all its steps, and the
                       fetch of its histories (every co-design mode)
  repro.descent.sync   that fetch, the descent's one host sync

One ``jax.monitoring`` listener, installed once the process has loaded
jax, counts jax's own tracing and compilation events and marks each with
a short span ``repro.jit.<counter>`` on the thread that caused it:

  retrace    a jaxpr trace (a new jit, a new shape, a new static value)
  compile    a backend compile, or a load from the persistent cache
  cache_hit  a load from the persistent compilation cache

The program bumps one counter of its own through ``count``:

  names_built  rows whose name string was formatted (an index-named
               ``MachineBatch`` formats its names only when they are read)

``counters()`` returns the counts so far.  The numpy-only paths never
import jax through this module: before jax is loaded ``span`` is a null
context and the jit counters read zero.
"""

from __future__ import annotations

import contextlib
import functools
import sys
import threading
from typing import Dict

PREFIX = "repro."

#: jax's monitoring events, and the counter each one bumps.
EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "retrace",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_hits": "cache_hit",
}

_lock = threading.Lock()
_counts: Dict[str, int] = {name: 0 for name in EVENTS.values()}
_counts["names_built"] = 0
_installed = False


def _on_event(event: str, *args, **kwargs) -> None:
    """The listener: jax calls it with an event's name (and, for a
    duration event, the seconds it took)."""
    counter = EVENTS.get(event)
    if counter is None:
        return
    with _lock:
        _counts[counter] += 1
    with sys.modules["jax"].profiler.TraceAnnotation(
            f"{PREFIX}jit.{counter}"):
        pass


def _loaded_jax():
    """jax if this process has loaded it (the listener then installed),
    else None."""
    global _installed
    jax = sys.modules.get("jax")
    if jax is None or _installed:
        return jax
    with _lock:
        if not _installed:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            jax.monitoring.register_event_listener(_on_event)
            _installed = True
    return jax


def span(name: str):
    """Context manager naming the enclosed host work ``repro.<name>``."""
    jax = _loaded_jax()
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(PREFIX + name)


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def count(counter: str, n: int) -> None:
    """Add ``n`` to one of the program's own counters."""
    with _lock:
        _counts[counter] += n


def counters() -> Dict[str, int]:
    """The retrace, compile and cache-hit counts since the listener was
    installed, and the program's own counts (a copy)."""
    _loaded_jax()
    with _lock:
        return dict(_counts)
