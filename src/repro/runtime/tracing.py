"""The serving program's own spans and counters, on the profiler's clock.

``span(name, **stats)`` marks a stretch of host work as a
``jax.profiler.TraceAnnotation`` named ``"repro." + name``.  The spans are
TraceMe events, so they share the profiler's clock with the device planes:
an idle gap on the device falls inside the named host work that caused it.
Keyword ``stats`` are written as the event's stats; a stat given as a
zero-argument callable is evaluated only while a profiler records, so
costly stats (per-slot lists) cost nothing otherwise; one that evaluates
to None is left out of the event.

A recording profiler is the only switch: with none recording, ``span``
returns one shared no-op context manager and evaluates nothing.  The
context manager's ``set_metadata(**stats)`` adds stats known only at the
end of the span (on the no-op it does nothing).

``builds()`` counts the programs built (compiled or loaded from the
compilation cache) by this process, always on, from ``jax.monitoring``'s
backend-compile event.

The spans and stats, by name (stats in parentheses):

* ``repro.service.intake`` (``n``): the frontend worker's inbox drain and
  its ``submit`` calls; ``repro.service.wait``: its idle wait.
* ``repro.sched.round`` (``step``, ``t_mono_ns``): one scheduler round;
  ``t_mono_ns`` is ``time.monotonic_ns()`` at entry, which maps the
  program's ``time.monotonic`` stamps onto the trace clock.
* inside a round: ``repro.sched.retire``, ``repro.sched.admit``
  (``admitted``), ``repro.sched.capacity`` (block allocation and
  preemption), ``repro.sched.dispatch`` (the engine call; its stats below),
  ``repro.sched.sync`` (the host waiting for the device) and
  ``repro.sched.emit`` (token hand-off and ``on_token``).
* ``repro.sched.dispatch``: ``kind`` (``decode``, ``mixed``, ``spec``,
  ``prefill``, ``chunk``, ``migrate``), ``n`` (steps), ``step``,
  ``live_tokens`` (live context summed over the steps), ``ctx`` and
  ``rem`` (per decoding slot, space-separated: context at step 0 and token
  budget), ``chunks`` (``start:len`` per prefill chunk), ``built``
  (programs built during the call) and, where the decode and mixed
  programs run the paged decode kernel, ``kv_steps`` (its steps per layer
  that copy and compute for the decoding slots, at step 0) and
  ``kv_grid`` (slots x the steps of a whole view: a walk of every slot's
  whole view, per layer).
"""
from __future__ import annotations

import jax

from jax.profiler import TraceAnnotation

PREFIX = "repro."
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Noop:
    """The span while no profiler records: enters, exits, records nothing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        pass


_NOOP = _Noop()


def span(name: str, **stats):
    """A ``repro.<name>`` span while a profiler records, else the no-op."""
    if not TraceAnnotation.is_enabled():
        return _NOOP
    stats = {k: v() if callable(v) else v for k, v in stats.items()}
    return TraceAnnotation(PREFIX + name, **{
        k: v for k, v in stats.items() if v is not None})


_built = 0


def _on_event(event: str, duration: float, **kwargs) -> None:
    global _built
    if event == _COMPILE_EVENT:
        _built += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


def builds() -> int:
    """Programs built (compiled or loaded) by this process so far."""
    return _built
