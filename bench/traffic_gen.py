"""The one generator that every traffic mix's data file parameterises.

A mix file (`bench/traffic/<mix>.json`) names its driver and gives the
numbers: utterance length law, audio rendering, batch and chunk sizes.
Everything here is a pure function of (mix, seed): the same seed gives
the same audio, lengths and order.

Lengths come from a fixed set per mix, not from fresh draws: the
`count` quantiles (i + 0.5) / count of the length law, in an order drawn
from the seed. So every seed offers the same work, and only its order
differs. The law is a log-normal given by its mean (the corpus's hours
over its utterances, as the mix file cites) and its sigma; its median is
mean * exp(-sigma^2 / 2).

Audio is rendered as the program's synthetic speech task does (a copy of
`repro.data.speech`'s renderer, vectorised): each label owns a random
mel prototype, emitted for `min_dur`..`max_dur` frames with optional
silence gaps, plus Gaussian noise.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, *stream) -> np.random.Generator:
  """A generator for one named stream of one seed (seeds of any width)."""
  return np.random.default_rng([int(seed), *[abs(hash_str(s)) for s in stream]])


def hash_str(s) -> int:
  """A stable (process-independent) 32-bit hash of a string or int."""
  if isinstance(s, int):
    return s & 0xFFFFFFFF
  h = 2166136261
  for ch in str(s).encode():
    h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
  return h


def length_set(law: dict) -> list:
  """The mix's fixed set of lengths in seconds: `count` quantiles of a
  log-normal of the given mean and sigma, clipped to [min, max]."""
  n = int(law["count"])
  sigma = law["sigma"]
  mu = math.log(law["mean"]) - sigma * sigma / 2
  out = []
  for i in range(n):
    z = NormalDist().inv_cdf((i + 0.5) / n)
    out.append(min(max(math.exp(mu + sigma * z), law["min"]), law["max"]))
  return out


def lengths_in_order(law: dict, seed: int, stream: str,
                     frame_rate: float) -> list:
  """The length set in frames, in the order that `seed` draws."""
  frames = [int(round(s * frame_rate)) for s in length_set(law)]
  order = rng_for(seed, stream).permutation(len(frames))
  return [frames[i] for i in order]


def prototypes(audio: dict, seed: int, feat_dim: int) -> np.ndarray:
  return rng_for(seed, "prototypes").standard_normal(
      (audio["vocab"], feat_dim)).astype(np.float32)


def render(audio: dict, protos: np.ndarray, frames: int,
           rng: np.random.Generator) -> tuple:
  """One utterance of exactly `frames` mel frames -> (feats, labels).

  Phones of `min_dur`..`max_dur` frames (label 1..vocab-1), each after a
  silence of 1-2 frames with probability `silence_prob`, fill the
  utterance; the rest of the last phone is cut at `frames`."""
  mean = (audio["min_dur"] + audio["max_dur"]) / 2 + 1.5 * audio["silence_prob"]
  n = int(frames / mean) + 8
  labels = rng.integers(1, audio["vocab"], size=n)
  durs = rng.integers(audio["min_dur"], audio["max_dur"] + 1, size=n)
  gaps = np.where(rng.random(n) < audio["silence_prob"],
                  rng.integers(1, 3, size=n), 0)
  starts = np.cumsum(gaps + durs) - durs            # phone start frames
  ids = np.zeros((frames,), np.int64)               # 0 = silence
  for lab, s, d in zip(labels, starts, durs):
    if s >= frames:
      break
    ids[s:s + d] = lab
  protos0 = np.concatenate([np.zeros((1, protos.shape[1]), np.float32),
                            protos[1:]], axis=0)
  feats = protos0[ids] + rng.standard_normal(
      (frames, protos.shape[1])).astype(np.float32) * audio["noise"]
  kept = labels[starts + durs <= frames]
  return feats.astype(np.float32), kept.astype(np.int32)


def utterances(audio: dict, seed: int, stream: str, feat_dim: int,
               frame_counts: list) -> list:
  """[(feats (t, feat_dim), labels)] for each frame count, from `seed`."""
  protos = prototypes(audio, seed, feat_dim)
  rng = rng_for(seed, stream)
  return [render(audio, protos, t, rng) for t in frame_counts]
