"""Spans of the program's hot paths, recorded only under a profiler.

    with tracing.span("speech.frame_step", live=n):
      ...

A span records while a JAX profiler session is active (the benchmark's
`--trace 1` run, or an operator's `jax.profiler.trace`); there is no
other switch. It then enters a `jax.profiler.TraceAnnotation` of its
name, so it lands in the profile beside the device ops on the profiler's
clock, with its counts as the event's metadata, and it appends one
`Record` to a bounded in-process buffer that `records()` returns. With
no session, `span` returns one shared null handle: one `is_enabled()`
call and no clock read; the only allocation is the keyword dict of a
call that passes counts.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

MAX_RECORDS = 65536

_Annotation = jax.profiler.TraceAnnotation
_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_ids = itertools.count(1)
_local = threading.local()


class Record(NamedTuple):
  name: str
  id: int
  parent: Optional[int]       # id of the enclosing span; None for a root
  start_ns: int               # time.perf_counter_ns()
  end_ns: int
  counts: dict


class _Span:
  __slots__ = ("name", "counts", "id", "parent", "start_ns", "end_ns",
               "_annotation", "_stack")

  def __init__(self, name: str, counts: dict):
    self.name, self.counts = name, counts
    self.start_ns = self.end_ns = None

  def stamp(self, start_ns: Optional[int] = None,
            end_ns: Optional[int] = None) -> None:
    """Take the caller's own `perf_counter_ns` readings as the record's
    start and/or end, so a caller that times the same interval keeps one
    timer."""
    if start_ns is not None:
      self.start_ns = start_ns
    if end_ns is not None:
      self.end_ns = end_ns

  def __enter__(self):
    stack = self._stack = _stack()
    self.parent = stack[-1].id if stack else None
    self.id = next(_ids)
    stack.append(self)
    self._annotation = _Annotation(self.name)
    self._annotation.__enter__()
    self.start_ns = time.perf_counter_ns()
    return self

  def __exit__(self, *exc) -> None:
    end_ns = time.perf_counter_ns() if self.end_ns is None else self.end_ns
    if self.counts:
      self._annotation.set_metadata(**self.counts)
    self._annotation.__exit__(*exc)
    self._stack.pop()
    _records.append(Record(self.name, self.id, self.parent, self.start_ns,
                           end_ns, self.counts))


class _NullSpan:
  __slots__ = ()

  def stamp(self, start_ns=None, end_ns=None) -> None:
    pass

  def __enter__(self):
    return self

  def __exit__(self, *exc) -> None:
    pass


_NULL = _NullSpan()


def _stack() -> list:
  stack = getattr(_local, "stack", None)
  if stack is None:
    stack = _local.stack = []
  return stack


def span(name: str, **counts):
  """A span named `name` with integer `counts`; a no-op without a
  profiler session."""
  if not _Annotation.is_enabled():
    return _NULL
  return _Span(name, counts)


def records() -> list:
  """A snapshot of the held records (at most MAX_RECORDS, oldest first,
  in the order the spans ended)."""
  return list(_records)
