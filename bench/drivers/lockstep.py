"""Live streams in lockstep through `StreamingSpeechServer.process_chunk`.

`channels` streams advance together at real time: every `chunk_frames`
mel frames (at `frame_rate` frames/s) the driver waits for the chunk's
due time (when its last frame has arrived) and calls `process_chunk`;
the last chunk of an utterance goes with `final=True`, which flushes the
frontend, and `reset()` starts the next round. A round is one utterance
per channel, of one length from the mix's length set; a cycle is the
whole set, in seed order. Due times run on one continuous audio clock,
so a late call makes every later chunk late. The window serves whole
cycles, as many as cover --seconds of audio: at any --seconds every seed
serves the same rounds (and so the same end-of-utterance flushes, which
cost about twice a chunk), in another order.

End-to-end: `stream_lat_p95_ms`, the 95th percentile over every chunk of
every stream of (return of the call that emits its labels - due time).
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import ds2, traffic_gen
from bench import harness
from bench.harness import BenchError
from bench.kernels import ds2_step

LEAD_S = 0.05    # the first chunk is due this long after the window opens


class Run:
  def __init__(self, cell, seed: int, seconds: float, tracing: bool,
               variant: str = ""):
    self.cell, self.seed, self.seconds = cell, seed, seconds
    self.config, self.mix = cell.config, cell.traffic
    self.variant = variant
    self.span = harness.spans(tracing)
    self.slots = int(self.mix["channels"])
    self.chunk = int(self.mix["chunk_frames"])
    self.rate = float(self.mix["frame_rate"])

  # -- set-up ---------------------------------------------------------------

  def setup(self) -> dict:
    import jax

    from repro.kernels import dispatch
    from repro.serving import StreamingSpeechServer

    cfg = ds2.program_config(self.config)
    self.form = self.config["form"]
    _, params = ds2.program_params(self.config, self.seed, self.form,
                                   self.variant)
    jax.block_until_ready(params)
    self.server = StreamingSpeechServer(
        cfg, params, batch_size=self.slots,
        kernel_policy=dispatch.decode_policy(self.slots))
    self.rounds = self._rounds()
    with dispatch.record_dispatch() as records:
      self._warm_up()
    info = {"rounds": len(self.rounds)}
    if not self.variant:
      info["regimes"] = ds2.check_routing(
          records, ds2.expected_regimes(self.config, self.form, self.slots))
    stats = self.server.compile_stats()
    if stats["frame_step"] != 1:
      raise BenchError(f"frame_step traced {stats['frame_step']} times")
    info["conv_buckets"] = [stats["conv1_buckets"], stats["conv2_buckets"]]
    return info

  def _rounds(self) -> list:
    """Round audio (channels, t, f) of whole cycles of the length set,
    as many as cover --seconds."""
    order = traffic_gen.lengths_in_order(self.mix["length_s"], self.seed,
                                         "rounds", self.rate)
    cycles = max(1, math.ceil(self.seconds * self.rate / sum(order)))
    rounds = []
    for i, t in enumerate(order * cycles):
      utts = traffic_gen.utterances(
          self.mix["audio"], self.seed, f"round{i}",
          self.config["feat_dim"], [t] * self.slots)
      rounds.append(np.stack([f for f, _ in utts]))
    return rounds

  def _warm_up(self) -> None:
    """One short round per final-chunk remainder: every conv window
    bucket and the frame step compile here, not in the window."""
    rng = np.random.default_rng(0)
    for r in range(self.chunk):
      t = 3 * self.chunk + r
      feats = rng.standard_normal(
          (self.slots, t, self.config["feat_dim"])).astype(np.float32)
      for s in range(0, t, self.chunk):
        self.server.process_chunk(feats[:, s:s + self.chunk],
                                  final=s + self.chunk >= t)
      self.server.reset()

  # -- the window -----------------------------------------------------------

  def window(self) -> dict:
    lat, call_s, call_max = [], 0.0, 0.0
    self.done = []                 # (round index, per-slot log-probs)
    delivered = 0                  # raw frames per stream, in the window
    flops = 0.0
    start = time.perf_counter() + LEAD_S
    clock = 0                      # audio frames due so far
    for ri, feats in enumerate(self.rounds):
      t = feats.shape[1]
      with self.span("bench.round"):
        for s in range(0, t, self.chunk):
          e = min(s + self.chunk, t)
          due = start + (clock + e - s) / self.rate
          wait = due - time.perf_counter()
          if wait > 0:
            with self.span("bench.wait_due"):
              time.sleep(wait)
          t0 = time.perf_counter()
          with self.span("bench.process_chunk"):
            self.server.process_chunk(feats[:, s:e], final=e == t)
          t1 = time.perf_counter()
          clock += e - s
          lat.append(t1 - due)
          call_s += t1 - t0
          call_max = max(call_max, t1 - t0)
        flops += ds2_step.forward_flops(self.config, self.form, t) \
            * self.slots
        delivered += t
        self.done.append((ri, [np.stack(s.log_probs)
                               for s in self.server._slots]))
        with self.span("bench.reset"):
          self.server.reset()
    end = time.perf_counter()
    audio_s = delivered * self.slots / self.rate
    return {
        "e2e": {"stream_lat_p95_ms": float(np.percentile(lat, 95)) * 1e3},
        "attempted": len(lat) * self.slots, "failed": 0,
        "window_s": end - start, "audio_s": audio_s, "busy_call_s": call_s,
        "model_flops": flops, "calls": len(lat),
        "lat_p50_ms": float(np.median(lat)) * 1e3,
        "lat_max_ms": float(np.max(lat)) * 1e3,
        "call_max_ms": call_max * 1e3,
    }

  def release(self) -> None:
    del self.server

  # -- correctness ----------------------------------------------------------

  def verify(self) -> list:
    """Served log-probs of a seed-drawn sample of streams, the longest
    finished round among them, against the f32 reference."""
    from bench.models import ds2_weights

    chk = self.mix["check"]
    if not self.done:
      raise BenchError("no round finished inside the window")
    rng = traffic_gen.rng_for(self.seed, "check")
    longest = max(range(len(self.done)),
                  key=lambda k: self.rounds[self.done[k][0]].shape[1])
    others = [k for k in range(len(self.done)) if k != longest]
    picked = [longest] + list(rng.permutation(others)[:chk["rounds"] - 1])
    items = []
    for k in picked:
      ri, lps = self.done[k]
      for ch in rng.permutation(self.slots)[:chk["streams"]]:
        items.append((self.rounds[ri][ch], lps[ch]))
    flat = ds2_weights.make(self.config, self.seed, self.form)
    return ds2.compare_streams(self.config, flat, items,
                               self.cell.limits,
                               chk["min_frames"])
