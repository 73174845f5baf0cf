"""The program's spans (`repro.runtime.tracing`) on the smoke DS2 config.

Without a profiler session nothing is recorded and serving is unchanged;
under one, the speech fleet's and the Trainer's spans nest as the
modules document, their counts agree with the server's own counters,
and the profile file holds the same spans with the same counts on a host
plane."""
import glob

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.tracenorm import RegularizerConfig
from repro.models.api import get_model
from repro.runtime import tracing
from repro.serving import StreamingSpeechServer
from repro.training import TrainConfig, Trainer

CHUNK = 8
SLOTS = 3
CHILDREN = {"speech.frontend", "speech.frame_step"}


@pytest.fixture(scope="module")
def speech():
  cfg = configs.get_smoke("deepspeech2-wsj")
  return cfg, get_model(cfg).init(jax.random.PRNGKey(0), cfg)


def _utts(cfg, lens):
  rng = np.random.default_rng(0)
  return [rng.standard_normal((t, cfg.feat_dim)).astype(np.float32)
          for t in lens]


def _newest_id() -> int:
  return max((r.id for r in tracing.records()), default=0)


def _traced(path, fn):
  """fn() under a profiler session: (its result, the records it made,
  the (name, counts) of every program span on the profile's host
  planes)."""
  newest = _newest_id()
  with jax.profiler.trace(str(path)):
    out = fn()
  recs = [r for r in tracing.records() if r.id > newest]
  [xplane] = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
  events = []
  for plane in jax.profiler.ProfileData.from_file(xplane).planes:
    if plane.name.startswith("/host:"):
      for line in plane.lines:
        events.extend((e.name, dict(e.stats)) for e in line.events
                      if e.name.startswith(("speech.", "train.")))
  return out, recs, events


@pytest.fixture(scope="module", params=["lockstep", "fleet"])
def served(request, speech, tmp_path_factory):
  """One surface served twice: untraced (which also compiles), then
  under a profiler session."""
  cfg, params = speech
  server = StreamingSpeechServer(cfg, params, batch_size=SLOTS)
  if request.param == "lockstep":
    feats = np.stack(_utts(cfg, [37] * SLOTS))
    calls = -(-feats.shape[1] // CHUNK)

    def serve():
      for s in range(0, feats.shape[1], CHUNK):
        server.process_chunk(feats[:, s:s + CHUNK],
                             final=s + CHUNK >= feats.shape[1])
      out = [np.stack(s.log_probs) for s in server._slots]
      server.reset()
      return out
  else:
    utts = _utts(cfg, [9, 23, 37, 17, 30])       # 5 on 3 slots: refills
    calls = 1

    def serve():
      for u in utts:
        server.submit(u)
      res = sorted(server.run(chunk_frames=CHUNK), key=lambda r: r.uid)
      return [r.log_probs for r in res]

  held = tracing.records()
  plain = serve()
  held_after = tracing.records()
  steps, busy = server.decode_steps, server.busy_steps
  traced, recs, events = _traced(tmp_path_factory.mktemp("xplane"), serve)
  return {"surface": request.param, "calls": calls, "plain": plain,
          "traced": traced, "held": (held, held_after), "recs": recs,
          "events": events,
          "decode_steps": server.decode_steps - steps,
          "busy_steps": server.busy_steps - busy,
          "utterances": len(plain)}


def _named(recs, name):
  return [r for r in recs if r.name == name]


def test_off_records_nothing_and_serves_the_same(served):
  held, held_after = served["held"]
  assert held_after == held
  assert tracing.span("a") is tracing.span("b", n=1)    # the null handle
  assert len(served["traced"]) == len(served["plain"]) > 0
  for a, b in zip(served["traced"], served["plain"]):
    np.testing.assert_array_equal(a, b)


def test_spans_nest(served):
  recs = served["recs"]
  roots = [r for r in recs if r.parent is None]
  root = ("speech.process_chunk" if served["surface"] == "lockstep"
          else "speech.run")
  assert [r.name for r in roots] == [root] * served["calls"]
  by_id = {r.id: r for r in recs}
  assert {r.name for r in recs if r.parent is not None} == CHILDREN
  for r in recs:
    if r.parent is not None:
      p = by_id[r.parent]
      assert p.parent is None
      assert p.start_ns <= r.start_ns <= r.end_ns <= p.end_ns


def test_counts_match_the_counters(served):
  recs = served["recs"]
  steps = _named(recs, "speech.frame_step")
  assert len(steps) == served["decode_steps"] > 0
  assert sum(r.counts["live"] for r in steps) == served["busy_steps"]
  assert all(1 <= r.counts["live"] <= SLOTS for r in steps)
  front = _named(recs, "speech.frontend")
  assert all(r.counts == {} for r in front)
  if served["surface"] == "lockstep":
    assert len(front) == served["calls"]       # one feed a chunk
  assert all(r.counts == {} for r in recs if r.parent is None)


def test_profile_holds_the_same_spans(served):
  def key(pairs):
    return sorted((n, sorted(c.items())) for n, c in pairs)
  assert key(served["events"]) == key(
      (r.name, r.counts) for r in served["recs"])


def test_trainer_step_spans(speech, tmp_path):
  cfg, _ = speech
  trainer = Trainer(cfg, TrainConfig(regularizer=RegularizerConfig(
      kind="trace", lambda_rec=1e-4, lambda_nonrec=1e-4)), batch_size=2)
  rng = np.random.default_rng(0)
  batch = {"feats": rng.standard_normal((2, 32, cfg.feat_dim)).astype(
               np.float32),
           "feat_lengths": np.array([32, 24], np.int32),
           "labels": np.array([[1, 2, 3, 0], [4, 5, 0, 0]], np.int32),
           "label_lengths": np.array([3, 2], np.int32)}
  trainer.train_step(batch)                      # compiles, untraced
  m, recs, events = _traced(tmp_path, lambda: trainer.train_step(batch))
  [root] = [r for r in recs if r.parent is None]
  assert root.name == "train.step" and root.counts == {}
  kids = {r.name: r for r in recs if r.parent == root.id}
  assert set(kids) == {"train.dispatch", "train.fetch"}
  fetch = kids["train.fetch"]
  [wait] = [r for r in recs if r.parent == fetch.id]
  assert wait.name == "train.wait"
  assert fetch.start_ns <= wait.start_ns <= wait.end_ns <= fetch.end_ns
  span_s = (fetch.end_ns - kids["train.dispatch"].start_ns) * 1e-9
  assert abs(m["wall_s"] - span_s) <= 1e-6
  assert root.start_ns <= kids["train.dispatch"].start_ns
  assert fetch.end_ns <= root.end_ns
  assert sorted(n for n, _ in events) == sorted(r.name for r in recs)


def test_buffer_is_bounded(tmp_path):
  """The buffer keeps the newest MAX_RECORDS records: filled with
  placeholders, ten real spans push the ten oldest out."""
  buf = tracing._records
  held = list(buf)
  filler = tracing.Record("filler", -1, None, 0, 0, {})
  try:
    buf.extend([filler] * tracing.MAX_RECORDS)
    with jax.profiler.trace(str(tmp_path)):
      for i in range(10):
        with tracing.span("bound.check", i=i):
          pass
    recs = tracing.records()
    assert len(recs) == tracing.MAX_RECORDS
    assert [r.counts for r in recs[-10:]] == [{"i": i} for i in range(10)]
    assert all(r is filler for r in recs[:-10])
  finally:
    buf.clear()
    buf.extend(held)
