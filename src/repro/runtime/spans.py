"""Host spans on the profiler's clock: the program's one tracing hook.

``span(name, **counts)`` marks one stage of a host path.  While a JAX
profiler session records, the stage lands in its trace as the host event
``repro.<name>`` on the calling thread, with ``counts`` (and whatever
``set_metadata`` adds before the span closes) as the event's stats; a
benchmark's trace reduction reads both on the clock it shares with the
device.  With no session a span costs about a microsecond.

Counts must be numbers already at hand (lengths, ``nbytes`` of a few
arrays): a span never runs a reduction of its own.  Where JAX has not
been imported (the NumPy engines) every span is one shared no-op, so a
span never imports JAX.
"""

from __future__ import annotations

import sys

PREFIX = "repro."


class _NoSpan:
    """The span where no profiler can be running: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **counts) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(name: str, **counts):
    """A context naming one host stage ``repro.<name>`` in a profiler
    trace; its ``set_metadata(**counts)`` adds counts known only at the
    stage's end."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)


def scope(name: str):
    """A ``jax.named_scope`` ``repro.<name>`` around traced model code: it
    lands in the ``op_name`` metadata of every HLO instruction made inside
    it (forward, backward and recomputation alike), which is how device
    ops in a profiler trace are put down to the model's layers."""
    import jax
    return jax.named_scope(PREFIX + name)
