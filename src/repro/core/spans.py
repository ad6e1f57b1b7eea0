"""Named spans at the program's layer boundaries, on the profiler's clock.

``span(name, **counts)`` is a ``jax.profiler.TraceAnnotation`` named
``"scalana." + name``: inert unless a ``jax.profiler`` trace is recording,
and then an event on the trace's host plane, beside the device's
operations and on the same clock.  ``counts`` (and whatever the body
passes to ``set_metadata`` once it knows them) become the event's stats.
Without jax in the process there is no profiler to record, so the span is
a null context and the analysis layer stays importable without jax.

``NAMES`` lists every span name the program emits, without the prefix.

``scope(name)`` is the device-side counterpart: a ``jax.named_scope``
that puts ``name`` into the name stack of every operation traced inside
it, and so into the HLO metadata (``op_name``) and the device trace's op
stats.  ``SCOPES`` lists every such name.
"""
from __future__ import annotations

import contextlib
import functools
import sys

NAMES = (
    "trainer.step", "trainer.batch",
    "profiler.compiled_step", "profiler.sampled_step", "profiler.fence",
    "store.apply_rows", "store.stack",
    "detect.non_scalable", "detect.abnormal", "detect.concat",
    "detect.readback", "feed.refresh",
    "backtrack", "root_causes", "report.render",
)

SCOPES = ("hybrid.shared_block",)


class _Inert(contextlib.nullcontext):
    def __enter__(self):
        return self

    def set_metadata(self, **_counts) -> None:
        pass


_INERT = _Inert()


def span(name: str, **counts):
    jax = sys.modules.get("jax")
    if jax is None:
        return _INERT
    return jax.profiler.TraceAnnotation("scalana." + name, **counts)


def spanned(name: str):
    """Decorator: the whole call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def scope(name: str):
    """``jax.named_scope(name)`` for a name in ``SCOPES``; called only by
    code that traces jax programs."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not in SCOPES {SCOPES}")
    import jax
    return jax.named_scope(name)
