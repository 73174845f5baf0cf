"""Continuous-batching speech fleet tests.

Parity is the whole contract, at two strictnesses:

* chunked streaming == full-utterance `deepspeech.forward` for ANY
  utterance length — including lengths that are NOT multiples of the
  conv time stride (the old frontend asserted stride alignment at
  flush). Pinned on a verified seed: per-frame `decode_step` and the
  time-batched training scan are independently-associated float
  programs, so greedy argmax can legitimately flip on near-tie frames
  at random init; the grid pins seeds/lengths where the two agree so a
  failure means a REAL frontend/state bug, not float noise.

* fleet scheduling == serial decoding, bitwise. Both sides run the
  same masked `frame_step` program, so continuous batching (staggered
  admits, retires, refills, masked dead slots) must be token-for-token
  identical to a dedicated batch-1 server — for any length mix, any
  chunking, both kernel policies, float and PTQ int8.

Plus the jit-signature pins (`compile_stats`): one masked frame-step
signature ever, slot insertion traced once, conv windows bucketed.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import deepspeech
from repro.models.api import get_model
from repro.serving import StreamingSpeechServer

#: deliberately stride-hostile lengths (time_stride totals 4 across the
#: two convs): primes, pow2±1, and exact multiples mixed together
PARITY_LENS = (1, 3, 4, 7, 9, 16, 17, 23, 31, 33, 40, 47, 48)


@pytest.fixture(scope="module")
def speech():
  cfg = configs.get_smoke("deepspeech2-wsj")
  api = get_model(cfg)
  params = api.init(jax.random.PRNGKey(0), cfg)
  return cfg, params


def _collapse(best_row):
  prev, out = -1, []
  for lab in best_row:
    if lab != 0 and lab != prev:
      out.append(int(lab))
    prev = lab
  return out


def _full_forward_labels(params, feats, cfg):
  lp = deepspeech.forward(params, jnp.asarray(feats[None]), cfg)
  return _collapse(np.asarray(jnp.argmax(lp, -1))[0])


def _serial_labels(cfg, params, utts, *, policy=None, chunk=7):
  """Oracle: each utterance alone through a batch-1 fleet."""
  srv = StreamingSpeechServer(cfg, params, batch_size=1,
                              kernel_policy=policy)
  for u in utts:
    srv.submit(u)
  return {r.uid: list(r.labels) for r in srv.run(chunk_frames=chunk)}


# ---------------------------------------------------------------------------
# chunked == full forward, every length class
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chunked_matches_full_forward_any_length(speech):
  """The fixed-left-pad frontend + pad-and-mask flush make streamed CTC
  labels equal the full-utterance forward for lengths that are NOT
  stride multiples (the old flush asserted `t % (2 * time_stride) == 0`
  and crashed on them). Label-exact, so the model runs in float32: the
  bf16 smoke model's greedy labels sit on near-ties that the two
  differently associated programs may break differently (bf16 is held
  to a log-prob tolerance in the test below)."""
  cfg, _ = speech
  cfg = dataclasses.replace(cfg, dtype=jnp.float32)
  params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
  rng = np.random.RandomState(0)
  for t in PARITY_LENS:
    feats = rng.randn(1, t, cfg.feat_dim).astype(np.float32)
    ref = _full_forward_labels(params, feats[0], cfg)
    srv = StreamingSpeechServer(cfg, params, batch_size=1)
    srv.submit(feats[0])
    (res,) = srv.run(chunk_frames=5)
    assert list(res.labels) == ref, f"t={t}"
    assert res.frames == t            # input mel frames, fully consumed


#: |d log-prob| bound, in nats, between the streamed per-frame step and
#: the time-batched forward: the two are independently associated float
#: programs over bf16 activations, so they agree to a few bf16 ulps of
#: O(1) logits (measured: 0.0035 at the smoke width, either policy)
LOGPROB_ATOL = 0.02


@pytest.mark.parametrize("policy", [None, "pallas"])
def test_fleet_log_probs_match_full_forward(speech, policy):
  """Each SpeechResult carries every frame's emissions, and they are the
  full-utterance forward's log-probs for any length mix and policy: the
  logit-level form of the chunked == full parity above."""
  cfg, params = speech
  rng = np.random.RandomState(0)
  utts = [rng.randn(t, cfg.feat_dim).astype(np.float32)
          for t in PARITY_LENS]
  srv = StreamingSpeechServer(cfg, params, batch_size=3,
                              kernel_policy=policy)
  uids = [srv.submit(u) for u in utts]
  results = {r.uid: r for r in srv.run(chunk_frames=7)}
  for uid, u in zip(uids, utts):
    ref = np.asarray(deepspeech.forward(params, jnp.asarray(u[None]),
                                        cfg))[0]
    got = results[uid].log_probs
    assert got.shape == ref.shape, (uid, got.shape, ref.shape)
    np.testing.assert_allclose(got, ref, rtol=0, atol=LOGPROB_ATOL)
    # the labels are the greedy collapse of the returned emissions
    assert list(results[uid].labels) == _collapse(got.argmax(-1))


@pytest.mark.slow
def test_lockstep_flush_non_multiple_length(speech):
  """The legacy lockstep surface handles a non-stride-multiple tail the
  same way: flush pads the residual window instead of asserting."""
  cfg, params = speech
  rng = np.random.RandomState(0)
  t = 23                                   # 23 % 4 != 0
  feats = rng.randn(2, t, cfg.feat_dim).astype(np.float32)
  ref = [_full_forward_labels(params, feats[i], cfg) for i in range(2)]
  srv = StreamingSpeechServer(cfg, params, batch_size=2)
  got = [[], []]
  for chunk in np.split(feats, [9, 16], axis=1):   # uneven chunking too
    for i, e in enumerate(srv.process_chunk(chunk)):
      got[i].extend(e)
  for i, e in enumerate(srv.flush()):
    got[i].extend(e)
  assert got == ref
  # flush is idempotent and terminal until reset()
  assert srv.flush() == [[], []]
  with pytest.raises(RuntimeError, match="reset"):
    srv.process_chunk(feats[:, :4])


# ---------------------------------------------------------------------------
# fleet == serial, bitwise
# ---------------------------------------------------------------------------

#: more utterances than slots, mixed stride-hostile lengths: admits are
#: staggered (each retire refills mid-decode of the survivors)
FLEET_LENS = (17, 9, 31, 4, 23, 40)


@pytest.mark.slow
@pytest.mark.parametrize("policy", [None, "pallas"])
def test_fleet_matches_serial(speech, policy):
  cfg, params = speech
  rng = np.random.RandomState(0)
  utts = [rng.randn(t, cfg.feat_dim).astype(np.float32)
          for t in FLEET_LENS]
  serial = _serial_labels(cfg, params, utts, policy=policy)

  srv = StreamingSpeechServer(cfg, params, batch_size=2,
                              kernel_policy=policy)
  uids = [srv.submit(u) for u in utts]
  results = {r.uid: r for r in srv.run(chunk_frames=7)}
  assert sorted(results) == sorted(uids)
  for uid in uids:
    assert list(results[uid].labels) == serial[uid]

  # per-stream CTC collapse state: stream i's labels must also equal a
  # fleet where it is the ONLY utterance (no cross-stream prev leakage,
  # no stale prev on the slot its retire freed for a refill)
  solo = _serial_labels(cfg, params, [utts[4]], policy=policy)
  assert list(results[uids[4]].labels) == solo[0]


@pytest.mark.slow
def test_fleet_matches_serial_int8(speech):
  """Continuous batching composes with PTQ: the masked frame step runs
  the int8_gemm regime and fleet == serial still holds bitwise."""
  from repro.quant import quantize_params
  cfg, params = speech
  qparams = quantize_params(params)
  rng = np.random.RandomState(0)
  utts = [rng.randn(t, cfg.feat_dim).astype(np.float32)
          for t in (17, 23, 9)]
  serial = _serial_labels(cfg, qparams, utts)
  srv = StreamingSpeechServer(cfg, qparams, batch_size=2)
  for u in utts:
    srv.submit(u)
  got = {r.uid: list(r.labels) for r in srv.run(chunk_frames=7)}
  assert got == serial


# ---------------------------------------------------------------------------
# jit-signature pins
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fleet_compile_stats_pin(speech):
  """One masked frame-step signature across an admit/retire/refill
  cycle with mixed lengths; slot surgery traced once; each conv stage
  exactly one signature per pow2 window bucket, whatever the number of
  live slots."""
  cfg, params = speech
  rng = np.random.RandomState(0)
  srv = StreamingSpeechServer(cfg, params, batch_size=2)
  for t in FLEET_LENS:
    srv.submit(rng.randn(t, cfg.feat_dim).astype(np.float32))
  results = srv.run(chunk_frames=7)
  assert len(results) == len(FLEET_LENS)
  stats = srv.compile_stats()
  assert stats["frame_step"] == 1
  assert stats["insert"] <= 1
  assert stats["conv1"] == len(stats["conv1_buckets"])
  assert stats["conv2"] == len(stats["conv2_buckets"])

  # a SECOND wave through the same server must add no signatures
  for t in (13, 29):
    srv.submit(rng.randn(t, cfg.feat_dim).astype(np.float32))
  srv.run(chunk_frames=4)
  stats2 = srv.compile_stats()
  assert stats2["frame_step"] == 1
  assert stats2["insert"] <= 1
  assert stats2["conv1"] == len(stats2["conv1_buckets"])

  # a wave with ONE live slot in the 2-slot server: the gathered conv
  # batch is `batch_size` rows however many slots are live, so the
  # number of live slots adds no conv signature
  srv.submit(rng.randn(FLEET_LENS[0], cfg.feat_dim).astype(np.float32))
  srv.run(chunk_frames=7)
  stats3 = srv.compile_stats()
  for key in ("conv1", "conv2", "conv1_buckets", "conv2_buckets"):
    assert stats3[key] == stats2[key], key
  assert stats3["frame_step"] == 1


def _count_conv_calls(srv):
  """Wrap the server's conv stages and its frontend feed: each feed
  records its number of final slots and, for every conv call made
  inside it, the stage and the live (non-zero) rows."""
  feeds, calls = [], []
  for stage in (1, 2):
    def shim(params, x, fn=getattr(srv, f"_conv{stage}"), stage=stage):
      rows = np.asarray(x, np.float32).reshape(x.shape[0], -1)
      calls.append((stage, int(np.any(rows != 0, axis=1).sum())))
      return fn(params, x)
    setattr(srv, f"_conv{stage}", shim)
  feed = srv._feed

  def counted(chunks, finals):
    finals, start = list(finals), len(calls)
    feed(chunks, finals)
    feeds.append((len(finals), calls[start:]))
  srv._feed = counted
  return feeds


@pytest.mark.parametrize("surface,slots", [("fleet", 1), ("fleet", 2),
                                           ("fleet", 3), ("lockstep", 3)])
def test_one_conv_call_per_stage_per_iteration(speech, surface, slots):
  """Each conv stage runs ONCE per fleet iteration (or lockstep chunk)
  over all live slots together, the flush windows of streams that end
  included: never once per slot. Fleet admits are staggered (more
  utterances than slots, mixed lengths), so iterations mix slots that
  are mid-stream, finishing and freshly admitted."""
  cfg, params = speech
  rng = np.random.RandomState(0)
  srv = StreamingSpeechServer(cfg, params, batch_size=slots)
  feeds = _count_conv_calls(srv)
  if surface == "fleet":
    for t in FLEET_LENS:
      srv.submit(rng.randn(t, cfg.feat_dim).astype(np.float32))
    assert len(srv.run(chunk_frames=7)) == len(FLEET_LENS)
  else:
    feats = rng.randn(slots, 23, cfg.feat_dim).astype(np.float32)
    for chunk in np.split(feats, [9, 16], axis=1):
      srv.process_chunk(chunk)
    srv.flush()
  assert feeds and any(n_final for n_final, _ in feeds)
  for _, stages in feeds:
    for stage in (1, 2):
      assert sum(1 for s, _ in stages if s == stage) <= 1, stages
  # the gathered call did carry every live slot at once
  assert max(live for _, stages in feeds
             for stage, live in stages if stage == 1) == slots


# ---------------------------------------------------------------------------
# lifecycle / surface hygiene (fast tier)
# ---------------------------------------------------------------------------


def test_submit_validates_and_modes_are_exclusive(speech):
  cfg, params = speech
  srv = StreamingSpeechServer(cfg, params, batch_size=2)
  with pytest.raises(ValueError):
    srv.submit(np.zeros((4, cfg.feat_dim + 1), np.float32))
  with pytest.raises(ValueError):
    srv.submit(np.zeros((cfg.feat_dim,), np.float32))   # missing time axis
  # lockstep engages the batch group; fleet submit must refuse
  srv2 = StreamingSpeechServer(cfg, params, batch_size=2)
  srv2.process_chunk(np.zeros((2, 8, cfg.feat_dim), np.float32))
  with pytest.raises(RuntimeError):
    srv2.submit(np.zeros((8, cfg.feat_dim), np.float32))
  # and a fleet-mode server must refuse lockstep chunks mid-run
  srv3 = StreamingSpeechServer(cfg, params, batch_size=1)
  srv3.submit(np.zeros((6, cfg.feat_dim), np.float32))
  srv3.run(chunk_frames=4)                  # run() completes -> mode clears
  srv3.process_chunk(np.zeros((1, 8, cfg.feat_dim), np.float32))


def test_conv_time_pads_convention():
  """pad_l fixed at (k - s) // 2, pad_r completes ceil(t / s) output
  frames — for every (t, k, s) the padded valid conv emits exactly
  ceil(t / s) frames, which is what makes streaming exact."""
  for k, s in ((5, 2), (11, 2), (3, 1), (7, 3)):
    for t in range(1, 40):
      pl, pr = deepspeech.conv_time_pads(t, k, s)
      assert pl == (k - s) // 2 and pr >= 0
      out = (t + pl + pr - k) // s + 1
      assert out == -(-t // s), (t, k, s)
