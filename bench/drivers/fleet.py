"""Offline transcription through the speech fleet: `submit` + `run`.

A drain queues the mix's length set of recordings (in a seed-drawn
order, with seeded audio) and calls `run(chunk_frames)`, which admits
them into `slots` slots, streams each through its own conv frontend and
retires it when done. The same drain repeats until the one that crosses
--seconds has finished; the window closes there (the audio's content
does not change the fleet's cost, so set-up renders one drain).

End-to-end: `transcribe_audio_s_per_s`, all audio seconds of the drains
over all the window's wall seconds.
"""
from __future__ import annotations

import time

import numpy as np

from bench import ds2, traffic_gen
from bench import harness
from bench.harness import BenchError
from bench.kernels import ds2_step


class Run:
  def __init__(self, cell, seed: int, seconds: float, tracing: bool,
               variant: str = ""):
    self.cell, self.seed, self.seconds = cell, seed, seconds
    self.config, self.mix = cell.config, cell.traffic
    self.variant = variant
    self.span = harness.spans(tracing)
    self.slots = int(self.mix["slots"])
    self.chunk = int(self.mix["chunk_frames"])
    self.rate = float(self.mix["frame_rate"])

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
    self.drain = self._drain()
    with dispatch.record_dispatch() as records:
      self._warm_up()
    info = {"drain": len(self.drain)}
    if not self.variant:
      info["regimes"] = ds2.check_routing(
          records, ds2.expected_regimes(self.config, self.form, self.slots))
    stats = self.server.compile_stats()
    if stats["frame_step"] != 1:
      raise BenchError(f"frame_step traced {stats['frame_step']} times")
    return info

  def _drain(self) -> list:
    frames = traffic_gen.lengths_in_order(self.mix["length_s"], self.seed,
                                          "drain0", self.rate)
    return [f for f, _ in traffic_gen.utterances(
        self.mix["audio"], self.seed, "drain0", self.config["feat_dim"],
        frames)]

  def _warm_up(self) -> None:
    """A drain with every final-chunk remainder, at full occupancy."""
    rng = np.random.default_rng(0)
    for r in range(self.slots):
      t = 3 * self.chunk + r % self.chunk
      self.server.submit(rng.standard_normal(
          (t, self.config["feat_dim"])).astype(np.float32))
    self.server.run(chunk_frames=self.chunk)

  def window(self) -> dict:
    self.results = []               # ({uid: index in drain}, [SpeechResult])
    utts = self.drain
    drain_audio = sum(u.shape[0] for u in utts) / self.rate
    drain_flops = sum(ds2_step.forward_flops(self.config, self.form,
                                             u.shape[0]) for u in utts)
    start = time.perf_counter()
    n = 0
    while time.perf_counter() - start < self.seconds:
      with self.span("bench.drain"):
        with self.span("bench.submit"):
          uids = {self.server.submit(u): k for k, u in enumerate(utts)}
        with self.span("bench.run"):
          res = self.server.run(chunk_frames=self.chunk)
      self.results.append((uids, res))
      n += 1
    window_s = time.perf_counter() - start
    audio, flops = n * drain_audio, n * drain_flops
    attempted = n * len(utts)
    done = sum(len(r) for _, r in self.results)
    return {
        "e2e": {"transcribe_audio_s_per_s": audio / window_s},
        "attempted": attempted, "failed": attempted - done,
        "window_s": window_s, "audio_s": audio, "model_flops": flops,
        "drains": n,
    }

  def release(self) -> None:
    del self.server

  def verify(self) -> list:
    """A seed-drawn sample of the transcribed utterances, the longest
    among them, against the f32 reference."""
    from bench.models import ds2_weights

    chk = self.mix["check"]
    pool = []
    for uids, res in self.results:
      for r in res:
        pool.append((self.drain[uids[r.uid]], r))
    if not pool:
      raise BenchError("no utterance finished inside the window")
    longest = max(range(len(pool)), key=lambda k: pool[k][0].shape[0])
    rng = traffic_gen.rng_for(self.seed, "check")
    rest = [k for k in rng.permutation(len(pool)) if k != longest]
    picked = [longest] + rest[:chk["utterances"] - 1]
    items = [(pool[k][0], pool[k][1].log_probs) for k in picked]
    flat = ds2_weights.make(self.config, self.seed, self.form)
    return ds2.compare_streams(self.config, flat, items,
                               self.cell.limits,
                               chk["min_frames"])
