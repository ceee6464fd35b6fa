"""Host spans around the calls into the program's layers, and XLA compile
counting.

A span wraps an attribute of a module or object for the length of a
``with`` block: each call is timed on the host clock and, while the
profiler runs, written into its trace as ``chipbench.<name>``.  Nothing in
the program is edited.
"""
from __future__ import annotations

import contextlib
import time

import jax


class Spans:
    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def wrap(self, owner, attr: str, name: str):
        inner = getattr(owner, attr)
        calls = self.seconds.setdefault(name, [])

        def timed(*args, **kwargs):
            with jax.profiler.TraceAnnotation("chipbench." + name):
                t0 = time.perf_counter()
                try:
                    return inner(*args, **kwargs)
                finally:
                    calls.append(time.perf_counter() - t0)

        setattr(owner, attr, timed)
        try:
            yield
        finally:
            setattr(owner, attr, inner)


class CompileClock:
    """Counts XLA backend compiles, and their seconds, while the block runs."""

    def __enter__(self):
        self.seconds, self.programs = 0.0, 0

        def listen(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.programs += 1

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)
