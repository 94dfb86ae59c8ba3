"""Host-side observation: named spans and bounded per-call records.

A span is a `jax.profiler.TraceAnnotation` named `repro.<name>`, so a
profiler trace shows it on the same clock as the device's operations; off
the profiler it costs one annotation enter and exit. A call record notes,
for one public call whose records something reads (`Booster.update`:
programs obtained per update; `PredictEngine.predict`: its `stats()`), its
name, its host-clock seconds and how many
programs JAX compiled or loaded from the persistent compilation cache
during it, keyed by the innermost span open when each one arrived:

    with obs.call("update") as rec:
        with obs.span("round.dispatch"):
            ...
    rec["compiles"]  # {"round.dispatch": 1, ...}

Records are kept in bounded deques (`MAXLEN` records a store): the
process-wide store read by `calls(name)`, or a store the caller owns
(`store()`, passed as `call(name, into=...)`). The device side is named
with `jax.named_scope` where the work is traced; those names reach the
trace as each operation's `tf_op`.
"""
from __future__ import annotations

import collections
import threading
import time
from contextlib import contextmanager

import jax

PREFIX = "repro."
MAXLEN = 1024

# What JAX reports once for each program it obtains, compiled or loaded
# from the persistent compilation cache: the duration event wraps both.
# (`/jax/compilation_cache/cache_hits` fires on a load besides, so
# counting it too would count a loaded program twice.)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Local(threading.local):
    def __init__(self):
        self.spans: list[str] = []
        self.calls: list[dict] = []


_local = _Local()
_store: dict[str, collections.deque] = {}
_store_lock = threading.Lock()


def store() -> collections.deque:
    """An empty bounded record store for a caller that keeps its own."""
    return collections.deque(maxlen=MAXLEN)


@contextmanager
def span(name: str):
    """Name the host work inside as `repro.<name>` in the profiler's trace."""
    with jax.profiler.TraceAnnotation(PREFIX + name):
        _local.spans.append(name)
        try:
            yield
        finally:
            _local.spans.pop()


@contextmanager
def call(name: str, into: collections.deque | None = None):
    """Record one public call; the body runs inside `span(name)`.

    Yields the record (a dict the caller may add fields to). A call that
    returns is stored in `into` if given, else in the process-wide store
    under `name`; a call that raises is not stored."""
    rec = {"name": name, "seconds": 0.0, "compiles": {}}
    if into is None:
        with _store_lock:
            into = _store.setdefault(name, store())
    t0 = time.perf_counter()
    _local.calls.append(rec)
    try:
        with span(name):
            yield rec
    finally:
        _local.calls.pop()
    rec["seconds"] = time.perf_counter() - t0
    into.append(rec)


def calls(name: str) -> list[dict]:
    """The stored records of calls named `name`, oldest first (at most
    MAXLEN)."""
    with _store_lock:
        return list(_store.get(name, ()))


def compiles(rec: dict) -> int:
    """Programs compiled or loaded from the cache during the call `rec`."""
    return sum(rec["compiles"].values())


def _on_duration(event: str, _secs: float, **_):
    """One program obtained on this thread: counted in the innermost open
    call, under the innermost open span."""
    if event == COMPILE_EVENT and _local.calls:
        key = _local.spans[-1]
        got = _local.calls[-1]["compiles"]
        got[key] = got.get(key, 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
