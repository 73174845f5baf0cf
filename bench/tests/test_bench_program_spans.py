"""The readers of the program's spans (`bench/program_spans.py` and the
six metric files over it) on synthetic records and a stub context."""
from __future__ import annotations

import sys
import types

import pytest

from bench import harness, program_spans
from repro.runtime import tracing

MS = 1_000_000                  # ns


class _Book:
  """Builds records as the tracer would: ids in order of entry, each
  record appended when its span ends (children before parents). A kid is
  (name, start, end, counts) or (name, start, end, counts, its kids)."""

  def __init__(self):
    self.recs, self.next_id = [], 1

  def root(self, name, start, end, kids=(), **counts):
    self._span(name, start, end, counts, kids, None)

  def _span(self, name, start, end, counts, kids, parent):
    rid = self.next_id
    self.next_id += 1
    for kname, ks, ke, kc, *grandkids in kids:
      self._span(kname, ks, ke, kc, grandkids[0] if grandkids else (), rid)
    self.recs.append(tracing.Record(name, rid, parent, start * MS, end * MS,
                                    counts))


def _ctx(cell: str, **window):
  return types.SimpleNamespace(cell=harness.Cell(cell), window=window)


@pytest.fixture
def held(monkeypatch):
  book = _Book()
  monkeypatch.setattr(tracing, "records", lambda: list(book.recs))
  return book


def _live_window(book):
  """A warm-up call, then two window calls of the lockstep surface."""
  book.root("speech.process_chunk", 0, 100,
            [("speech.frontend", 0, 50, {}),
             ("speech.frame_step", 50, 90, {"live": 2})])
  for t in (200, 300):
    book.root("speech.process_chunk", t, t + 20,
              [("speech.frontend", t, t + 6, {}),
               ("speech.frame_step", t + 6, t + 10, {"live": 2}),
               ("speech.frame_step", t + 11, t + 14, {"live": 1})])


def test_last_n_roots_drop_the_warm_up(held):
  _live_window(held)
  roots, recs = program_spans.window_records(_ctx("ds2-wsj.live16",
                                                  calls=2, audio_s=2.0))
  assert [r.start_ns for r in roots] == [200 * MS, 300 * MS]
  assert len(recs) == 6
  assert all(r.start_ns >= 200 * MS for r in recs)


def _transcribe_window(book):
  book.root("speech.run", 0, 50,                     # warm-up drain
            [("speech.frame_step", 10, 40, {"live": 1})])
  book.root("speech.run", 100, 400,
            [("speech.frontend", 110, 150, {}),
             ("speech.frame_step", 150, 160, {"live": 64}),
             ("speech.frontend", 160, 170, {}),
             ("speech.frame_step", 170, 185, {"live": 32})])


def _train_window(book):
  book.root("train.step", 0, 500,                    # set-up step
            [("train.dispatch", 0, 100, {}),
             ("train.fetch", 100, 500, {},
              [("train.wait", 100, 490, {})])])
  for t, host in ((1000, 7), (1100, 9)):
    book.root("train.step", t, t + 60,
              [("train.dispatch", t + 1, t + host, {}),
               ("train.fetch", t + host, t + 59, {},
                [("train.wait", t + host, t + 56, {})])])


CASES = [
    ("frontend_ms_per_audio_s.stream", "ds2-wsj.live16", _live_window,
     {"calls": 2, "audio_s": 2.0}, (6 + 6) / 2.0),
    ("step_ms_per_audio_s.stream", "ds2-wsj-r256.live16", _live_window,
     {"calls": 2, "audio_s": 2.0}, (4 + 3 + 4 + 3) / 2.0),
    ("frontend_ms_per_audio_s.transcribe", "ds2-wsj.transcribe64",
     _transcribe_window, {"drains": 1, "audio_s": 8.0}, 50 / 8.0),
    ("step_ms_per_audio_s.transcribe", "ds2-wsj.transcribe64",
     _transcribe_window, {"drains": 1, "audio_s": 8.0}, 25 / 8.0),
    ("slot_occupancy.transcribe", "ds2-wsj.transcribe64",
     _transcribe_window, {"drains": 1, "audio_s": 8.0}, 100.0 * 96 / 128),
    ("host_ms_per_step.train", "ds2-wsj.train32", _train_window,
     {"steps": 2}, ((60 - 49) + (60 - 47)) / 2),
]


@pytest.mark.parametrize("metric,cell,fill,window,want", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_arithmetic(held, metric, cell, fill, window, want):
  fill(held)
  ctx = _ctx(cell, **window)
  got = ctx.cell.metric_reader(metric).read(ctx)
  assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric,cell,fill,window,want", CASES,
                         ids=[c[0] for c in CASES])
def test_too_few_roots_read_nothing(held, metric, cell, fill, window, want):
  fill(held)
  key = next(k for k in window if k != "audio_s")
  ctx = _ctx(cell, **dict(window, **{key: window[key] + 2}))
  assert ctx.cell.metric_reader(metric).read(ctx) is None


def test_tracing_off_reads_nothing(held):
  ctx = _ctx("ds2-wsj.live16", calls=2, audio_s=2.0)   # nothing recorded
  assert program_spans.ms_per_audio_s(ctx, "speech.frontend") is None
  assert program_spans.slot_occupancy(ctx) is None


def test_program_without_tracer_reads_nothing(held, monkeypatch):
  _live_window(held)
  monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
  ctx = _ctx("ds2-wsj.live16", calls=2, audio_s=2.0)
  assert program_spans.ms_per_audio_s(ctx, "speech.frontend") is None


def test_dropped_spans_read_nothing(held, monkeypatch):
  _live_window(held)
  ctx = _ctx("ds2-wsj.live16", calls=2, audio_s=2.0)
  assert program_spans.window_records(ctx) is not None
  held.recs = held.recs[2:]       # the warm-up's spans went, its root
  monkeypatch.setattr(tracing, "MAX_RECORDS", len(held.recs))   # stays
  assert program_spans.window_records(ctx) is not None
  held.recs = held.recs[2:]       # now a span of the window went too
  monkeypatch.setattr(tracing, "MAX_RECORDS", len(held.recs))
  assert program_spans.window_records(ctx) is None
