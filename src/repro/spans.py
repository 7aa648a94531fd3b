"""Host spans and counters of the FedCD control plane.

The round engine records where its host time goes at its own layer
boundaries: ``span(name)`` around a piece of work, ``count(name, n)``
where the work is decided, and ``round_scope(t)`` at the start of round
``t`` so that every record of the round carries its id. Program span
names start with ``fedcd.``.

The recorder is off by default. Off, ``span`` tests one module flag and
returns the shared ``NULL`` context: nothing is allocated and no clock
is read. Turn it on from code::

    from repro import spans
    spans.enable()
    server.run(10)
    spans.disable()
    recorded = spans.drain()    # Recorded(spans=[Span, ...],
                                #          counts={round: {name: n}})

On, every span also enters ``jax.profiler.TraceAnnotation(name)``, so a
profiler trace taken meanwhile holds the span on the same clock as the
device's operations. Records stay in memory until ``drain`` hands them
out and clears them. The recorder is not thread-safe: the control plane
runs on one thread.
"""
from __future__ import annotations

import time
from typing import Dict, List, NamedTuple, Optional

import jax


class Span(NamedTuple):
    name: str
    parent: Optional[str]          # the enclosing span's name
    round: Optional[int]           # the round_scope in force
    start_ns: int                  # time.perf_counter_ns()
    end_ns: int


class Recorded(NamedTuple):
    spans: List[Span]
    counts: Dict[Optional[int], Dict[str, int]]   # round -> name -> total


class _Null:
    """The context ``span`` returns while the recorder is off."""
    __slots__ = ("end_ns",)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL = _Null()

_on = False
_round: Optional[int] = None
_open: List[str] = []
_spans: List[Span] = []
_counts: Dict[Optional[int], Dict[str, int]] = {}


class _Span:
    __slots__ = ("name", "start_ns", "end_ns", "_parent", "_ann")

    def __init__(self, name: str, start_ns: Optional[int]):
        self.name = name
        self.start_ns = start_ns
        self.end_ns: Optional[int] = None

    def __enter__(self):
        self._parent = _open[-1] if _open else None
        _open.append(self.name)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        if self.start_ns is None:
            self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = self.end_ns if self.end_ns is not None \
            else time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _open.pop()
        _spans.append(Span(self.name, self._parent, _round, self.start_ns,
                           end))
        return False


def span(name: str, start_ns: Optional[int] = None):
    """A context that records ``name`` from entry to exit. A caller that
    reads the clock itself passes ``start_ns`` and may set ``end_ns`` on
    the entered span; the record then carries those stamps."""
    if not _on:
        return NULL
    return _Span(name, start_ns)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the current round."""
    if _on:
        per_round = _counts.setdefault(_round, {})
        per_round[name] = per_round.get(name, 0) + n


def round_scope(t: int) -> None:
    """Tag every span and count recorded from now on with round ``t``."""
    global _round
    _round = t


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def drain() -> Recorded:
    """Hand out the recorded spans (in the order they closed) and
    counters, and clear them."""
    global _spans, _counts
    out = Recorded(_spans, _counts)
    _spans, _counts = [], {}
    return out
