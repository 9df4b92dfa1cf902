"""Named host spans in the profiler's trace, switched by ``VLIWJit.spans``.

On, ``span(True, name)`` is ``jax.profiler.TraceAnnotation(name)``: the
span lands in the profiler's trace, on the device trace's clock, so a gap
in the device's work can be put down to what the host was doing. Off (the
default), it returns one shared no-op context and constructs nothing.

``spanned(name)`` runs a method of an object that holds the JIT as ``jit``
(the serving engine, a JIT session) inside that span. A name never carries
a request or tenant id: a trace reduction matches names exactly.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable

import jax

_OFF = contextlib.nullcontext()


def span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else _OFF


def spanned(name: str) -> Callable[[Callable], Callable]:
    def wrap(method: Callable) -> Callable:
        @functools.wraps(method)
        def call(self, *args, **kwargs):
            with span(self.jit.spans, name):
                return method(self, *args, **kwargs)
        return call
    return wrap
