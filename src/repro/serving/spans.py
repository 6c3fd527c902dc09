"""Named host spans of the serving engine, on the profiler's clock.

Spans are off by default: `span()` then returns one shared no-op
context manager, builds nothing and evaluates no argument.  With
`enable(True)` each span is a `jax.profiler.TraceAnnotation` named
`serve.<name>`.  The profiler writes it into the same trace as the
device's operations, so a gap in which the device is idle can be put
down to the span that was open at the time.  A span never waits for the
device: where the host waits is the same with spans on or off.

An argument given as a zero-argument callable is called only when spans
are on, so a site can pass a value that costs something to compute.
`set_metadata(**args)` on the object `span()` returns adds arguments
known only once the span's work is done; the no-op span ignores them.

`profile(trace_dir)` turns spans on and records a JAX profiler trace
into `trace_dir` while it is open (`python -m repro.launch.serve
--profile DIR`).
"""
from __future__ import annotations

import contextlib

import jax

PREFIX = "serve."


class _NoSpan:
    """The shared span used while spans are off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **args) -> None:
        pass


NO_SPAN = _NoSpan()
_on = False


def enable(on: bool) -> None:
    global _on
    _on = bool(on)


def enabled() -> bool:
    return _on


def span(name: str, **args):
    """`serve.<name>` with `args` while spans are on, else `NO_SPAN`."""
    if not _on:
        return NO_SPAN
    return jax.profiler.TraceAnnotation(
        PREFIX + name, **{k: v() if callable(v) else v for k, v in args.items()})


@contextlib.contextmanager
def profile(trace_dir: str):
    """Spans on, and a JAX profiler trace written under `trace_dir`, for
    the body of the `with`; spans go back to their former state after."""
    was = _on
    enable(True)
    try:
        with jax.profiler.trace(trace_dir):
            yield
    finally:
        enable(was)
