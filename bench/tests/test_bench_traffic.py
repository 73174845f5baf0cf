"""Each traffic mix is a pure function of its file and the seed."""
from __future__ import annotations

import numpy as np
import pytest

from bench import harness, traffic_gen

BIG = 2 ** 33 + 12345          # wider than 32 bits, as the driver's seeds


def mixes():
  return sorted({w["traffic"] for w in harness.benchmark()["workloads"]})


@pytest.mark.parametrize("mix", mixes())
def test_length_order_is_deterministic_and_a_permutation(mix):
  cell = next(harness.Cell(w["name"]) for w in
              harness.benchmark()["workloads"] if w["traffic"] == mix)
  law, rate = cell.traffic["length_s"], cell.traffic["frame_rate"]
  a = traffic_gen.lengths_in_order(law, BIG, "rounds", rate)
  b = traffic_gen.lengths_in_order(law, BIG, "rounds", rate)
  c = traffic_gen.lengths_in_order(law, BIG + 1, "rounds", rate)
  assert a == b
  assert sorted(a) == sorted(c)          # every seed offers the same work
  assert a != c                          # in another order
  secs = traffic_gen.length_set(law)
  assert law["min"] <= min(secs) and max(secs) <= law["max"]


@pytest.mark.parametrize("mix", mixes())
def test_audio_is_deterministic_in_the_seed(mix):
  cell = next(harness.Cell(w["name"]) for w in
              harness.benchmark()["workloads"] if w["traffic"] == mix)
  audio, f = cell.traffic["audio"], cell.config["feat_dim"]
  one = traffic_gen.utterances(audio, BIG, "s", f, [137, 64])
  two = traffic_gen.utterances(audio, BIG, "s", f, [137, 64])
  other = traffic_gen.utterances(audio, BIG + 1, "s", f, [137, 64])
  for (fa, la), (fb, lb), (fc, _) in zip(one, two, other):
    np.testing.assert_array_equal(fa, fb)
    np.testing.assert_array_equal(la, lb)
    assert not np.array_equal(fa, fc)
  assert [x.shape for x, _ in one] == [(137, f), (64, f)]
  assert all(len(lab) > 0 and lab.min() >= 1 for _, lab in one)


def test_training_labels_fit_ctc():
  """Every training utterance has enough output frames for its labels
  (CTC needs one frame per label and one between repeats)."""
  cell = harness.Cell("ds2-wsj.train32")
  mix = cell.traffic
  frames = traffic_gen.lengths_in_order(mix["length_s"], BIG, "batch0",
                                        mix["frame_rate"])
  utts = traffic_gen.utterances(mix["audio"], BIG, "batch0", 80, frames)
  for (feats, labels), t in zip(utts, frames):
    out = -(-(-(-t // 2)) // 2)
    repeats = int(np.sum(labels[1:] == labels[:-1]))
    assert len(labels) + repeats <= out
    assert t <= mix["frames"] and len(labels) <= mix["label_max"]
  secs = traffic_gen.length_set(mix["length_s"])
  # the law's mean, less what the clip at the padded length cuts off
  assert 7.3 <= sum(secs) / len(secs) <= mix["length_s"]["mean"]


@pytest.mark.parametrize("seconds", [5, 10, 20, 30, 51])
def test_live_window_serves_whole_cycles(seconds):
  """At any --seconds every seed's window holds the same rounds: whole
  cycles of the length set, as many as cover --seconds."""
  from bench.drivers import lockstep
  cell = harness.Cell("ds2-wsj.live16")
  cell.traffic = dict(cell.traffic, channels=1)
  cell.config = dict(cell.config, feat_dim=4)
  rate = cell.traffic["frame_rate"]
  cycle = sum(traffic_gen.lengths_in_order(cell.traffic["length_s"], BIG,
                                           "rounds", rate))
  got = []
  for seed in (BIG, BIG + 1, 7):
    rounds = lockstep.Run(cell, seed, seconds, False)._rounds()
    got.append(sorted(r.shape[1] for r in rounds))
  assert got[0] == got[1] == got[2]
  total = sum(got[0])
  assert total % cycle == 0
  assert total >= seconds * rate > total - cycle


def test_rng_streams_are_stable_across_processes():
  assert traffic_gen.hash_str("rounds") == 3082106712     # FNV-1a
  x = traffic_gen.rng_for(7, "a").integers(0, 1 << 30, 4)
  y = traffic_gen.rng_for(7, "a").integers(0, 1 << 30, 4)
  assert list(x) == list(y)
