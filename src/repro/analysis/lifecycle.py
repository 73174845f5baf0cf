"""Executing lifecycle checks: jit caches after real serve cycles.

Four checks live here — `retrace_stability` (the vanilla engine
lifecycle), `prefix_splice_stability` (the prefix-cache splice path
must not add prefill signatures beyond the cold path's, and spliced
greedy output must match cold token-for-token),
`spec_window_stability` (the batched speculative verify window compiles
exactly one signature per (bucket, k) — across greedy AND sampled
cycles and across mid-serve draft-rank walks, which retrace only
draft-side programs), and `speech_fleet_stability` (the continuous-
batching speech fleet: one masked frame-step signature across
admit/retire/refill with mixed non-stride-multiple utterance lengths,
bucketed conv windows, and fleet output token-identical to serial
per-utterance decoding).

Retrace-stability: the engine's jit caches after a real serve cycle.

Unlike the other checks this one must *execute* (tiny, smoke-scale,
batch 2, a handful of tokens): jit cache sizes only exist after calls.
The scenario is chosen to exercise every lifecycle edge that could
silently re-trace:

  * two prompt lengths in different pow2 buckets (admission prefill
    compiles per bucket — that is the contract, counted not flagged),
  * more requests than slots with tiny budgets, forcing retire ->
    refill-from-queue (insert_slot + a second admission prefill), and
  * enough decode steps that any shape drift in the donated state
    signature would show up as step cache > 1.

Invariants, per `LMEngine.compile_stats`:

  step == 1                                  one decode signature, ever
  prefill == len(prefill_buckets)            bucketed, nothing beyond
  replay, window, insert each <= 1           auxiliary programs stable

Families: the three token-driven LMs (qwen3, zamba2, xlstm) run the
LMEngine checks; deepspeech runs the speech-fleet check. Whisper
decodes against encoder memory the engine does not synthesize and is
not audited here.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple

import jax
import numpy as np

from repro import configs
from repro.analysis.report import Finding
from repro.analysis.targets import normalize_config
from repro.models.api import get_model
from repro.serving.engine import LMEngine, StreamingSpeechServer
from repro.serving.prefix_cache import PrefixCache
from repro.serving.speculative import RankController

#: configs whose family runs the full LMEngine lifecycle
LIFECYCLE_CONFIGS = ("qwen3-4b", "zamba2-7b", "xlstm-350m")

_VOCAB = 64
_BATCH = 2
_MAX_LEN = 16
#: lengths 3 and 6 pad into distinct pow2 buckets (4 and 8)
_PROMPT_LENS = (3, 6, 3)
_BUDGET = 3


def _serve_cycle(cfg, params, policy: str) -> dict:
  eng = LMEngine(cfg, params, batch_size=_BATCH, max_len=_MAX_LEN,
                 kernel_policy=None if policy == "jnp" else policy)
  rs = np.random.RandomState(0)
  for n in _PROMPT_LENS:      # 3 requests, 2 slots -> retire + refill
    eng.submit(rs.randint(1, _VOCAB, size=(n,)), max_new_tokens=_BUDGET)
  done = eng.run()
  assert len(done) == len(_PROMPT_LENS)
  return eng.compile_stats()


def check_retrace_stability(
    config_names: Iterable[str],
    policies: Iterable[str]) -> Tuple[List[Finding], List[dict]]:
  """Run the serve cycle for every requested lifecycle-capable config x
  policy; return (findings, per-run info rows)."""
  findings: List[Finding] = []
  infos: List[dict] = []
  for name in config_names:
    name = normalize_config(name)
    if name not in LIFECYCLE_CONFIGS:
      continue
    cfg = configs.get_smoke(name).with_(vocab_size=_VOCAB)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)

    for policy in policies:
      stats = _serve_cycle(cfg, params, policy)
      info = dict(config=name, policy=policy, quant="-",
                  program="lifecycle", compile_stats=stats)
      infos.append(info)

      def fail(key: str, detail: str) -> None:
        findings.append(Finding(
            check="retrace_stability", config=name, policy=policy,
            program="lifecycle", key=key, detail=detail))

      if stats["step"] != 1:
        fail(f"step-cache:{stats['step']}",
             f"decode step compiled {stats['step']} signatures across a "
             f"serve cycle (admit/decode/retire/refill) — the donated "
             f"state shape is not stable")
      n_buckets = len(stats["prefill_buckets"])
      if stats["prefill"] != n_buckets:
        fail(f"prefill-cache:{stats['prefill']}/buckets:{n_buckets}",
             f"prefill compiled {stats['prefill']} signatures but only "
             f"{n_buckets} (batch, bucket) shapes were admitted "
             f"({stats['prefill_buckets']}): a prompt shape escaped "
             f"bucketing")
      for prog in ("replay", "window", "insert", "draft_step0"):
        n = stats.get(prog, 0)
        if n > 1:
          fail(f"{prog}-cache:{n}",
               f"auxiliary program {prog!r} compiled {n} signatures in "
               f"one serve cycle")
  return findings, infos


# ---------------------------------------------------------------------------
# prefix_splice_stability
# ---------------------------------------------------------------------------

#: two shared-prefix buckets + an unrelated prompt, chosen so the warm
#: path's pieces land in exactly the cold path's buckets:
#:   A   full len 8            -> bucket 8 (cold and warm both)
#:   B   A[:4] + new suffix    -> cold bucket 8; warm fork-splits into
#:                                4 (template, published) + 4 (suffix)
#:   C   A[:4] + other suffix  -> cold bucket 8; warm splices B's fork
#:                                entry and prefills only bucket 4
#:   D   unrelated len 4       -> bucket 4 (cold and warm both)
#: so cold and warm prefill signature sets are both {(1,4), (1,8)} and
#: any extra warm signature is the splice path leaking a new jit shape.
#: Tokens are pinned (not drawn) so the prompts provably diverge right
#: at the fork and D shares no first token with A-C.
_SPLICE_PROMPTS = (
    (1, 2, 3, 4, 5, 6, 7, 8),
    (1, 2, 3, 4, 9, 10, 11, 12),
    (1, 2, 3, 4, 13, 14, 15, 16),
    (20, 21, 22, 23),
)


def _splice_cycle(cfg, params, policy: str, cache) -> Tuple[dict, dict]:
  """Serve the splice scenario; returns (uid -> tokens, compile_stats)."""
  eng = LMEngine(cfg, params, batch_size=_BATCH, max_len=_MAX_LEN,
                 kernel_policy=None if policy == "jnp" else policy,
                 prefix_cache=cache)
  for p in _SPLICE_PROMPTS:   # 4 requests, 2 slots -> retire + refill
    eng.submit(np.asarray(p, np.int32), max_new_tokens=_BUDGET)
  done = eng.run()
  assert len(done) == len(_SPLICE_PROMPTS)
  return ({f.uid: tuple(int(t) for t in f.tokens) for f in done},
          eng.compile_stats())


def check_prefix_splice_stability(
    config_names: Iterable[str],
    policies: Iterable[str]) -> Tuple[List[Finding], List[dict]]:
  """Cold vs cached-splice serve cycles over shared-prefix traffic.

  Invariants: the warm engine keeps the cold engine's compile contract
  (step == 1, prefill == len(prefill_buckets), aux programs <= 1), its
  prefill bucket SET equals the cold set (the splice path introduces no
  new jit signatures — the acceptance bar from ISSUE 7), the cache
  actually hit (otherwise the splice path silently never ran and the
  equality is vacuous), and warm greedy tokens equal cold greedy tokens
  for every request (splice is bit-exact, not just shape-stable).
  """
  findings: List[Finding] = []
  infos: List[dict] = []
  for name in config_names:
    name = normalize_config(name)
    if name not in LIFECYCLE_CONFIGS:
      continue
    cfg = configs.get_smoke(name).with_(vocab_size=_VOCAB)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)

    for policy in policies:
      cache = PrefixCache(capacity_mb=64)
      cold_toks, cold = _splice_cycle(cfg, params, policy, None)
      warm_toks, warm = _splice_cycle(cfg, params, policy, cache)
      cs = cache.stats()
      info = dict(config=name, policy=policy, quant="-",
                  program="lifecycle", check="prefix_splice_stability",
                  compile_stats=warm, cache_stats=cs)
      infos.append(info)

      def fail(key: str, detail: str) -> None:
        findings.append(Finding(
            check="prefix_splice_stability", config=name, policy=policy,
            program="lifecycle", key=key, detail=detail))

      if warm_toks != cold_toks:
        fail("token-parity",
             f"cached-splice greedy tokens diverged from cold serving "
             f"(cold {cold_toks} vs warm {warm_toks}) — the spliced "
             f"state is not bit-identical to the cold prefill state")
      if cs["hits"] < 1:
        fail("no-hits",
             f"the shared-prefix scenario produced no cache hits "
             f"({cs}) — the splice path never ran, so its stability "
             f"was not exercised")
      if set(warm["prefill_buckets"]) != set(cold["prefill_buckets"]):
        fail(f"prefill-signatures:{sorted(warm['prefill_buckets'])}",
             f"splice path changed the prefill signature set: cold "
             f"{sorted(cold['prefill_buckets'])} vs warm "
             f"{sorted(warm['prefill_buckets'])} — suffix/fork prefill "
             f"escaped the cold path's buckets")
      if warm["step"] != 1:
        fail(f"step-cache:{warm['step']}",
             f"decode step compiled {warm['step']} signatures in the "
             f"cached-splice cycle — splice surgery destabilized the "
             f"donated state shape")
      n_buckets = len(warm["prefill_buckets"])
      if warm["prefill"] != n_buckets:
        fail(f"prefill-cache:{warm['prefill']}/buckets:{n_buckets}",
             f"prefill compiled {warm['prefill']} signatures but only "
             f"{n_buckets} (batch, bucket) shapes were admitted "
             f"({warm['prefill_buckets']}): a spliced suffix escaped "
             f"bucketing")
      for prog in ("replay", "window", "insert", "draft_step0"):
        n = warm.get(prog, 0)
        if n > 1:
          fail(f"{prog}-cache:{n}",
               f"auxiliary program {prog!r} compiled {n} signatures in "
               f"the cached-splice cycle")
  return findings, infos


# ---------------------------------------------------------------------------
# spec_window_stability
# ---------------------------------------------------------------------------

#: speculative-cycle geometry: one k (= one window bucket per engine),
#: a low starting rank, and a deliberately unreachable accept-rate band
#: so the controller is guaranteed to walk the rank mid-serve
_SPEC_K = 2
_SPEC_RANK = 8
_SPEC_BUDGET = 4


def _spec_cycle(cfg, params, policy: str) -> Tuple[dict, int]:
  """One speculative engine through a greedy cycle then a sampled cycle,
  with a rank controller that must walk; returns (stats, rank walks)."""
  rc = RankController(band=(0.99, 1.0), step=32, interval=2,
                      min_rank=_SPEC_RANK, max_rank=_SPEC_RANK + 64)
  eng = LMEngine(cfg, params, batch_size=_BATCH, max_len=_MAX_LEN,
                 kernel_policy=None if policy == "jnp" else policy,
                 speculate=_SPEC_K, draft_rank=_SPEC_RANK,
                 rank_controller=rc)
  rs = np.random.RandomState(0)
  for temperature in (0.0, 0.7):     # verify must share ONE program
    eng.reset()
    for n in _PROMPT_LENS:           # retire + refill, two buckets
      eng.submit(rs.randint(1, _VOCAB, size=(n,)),
                 max_new_tokens=_SPEC_BUDGET)
    done = eng.run(temperature=temperature, rng=jax.random.PRNGKey(1))
    assert len(done) == len(_PROMPT_LENS)
  return eng.compile_stats(), len(eng.rank_history)


def check_spec_window_stability(
    config_names: Iterable[str],
    policies: Iterable[str]) -> Tuple[List[Finding], List[dict]]:
  """The batched verify window must compile exactly ONE signature per
  (bucket, k) engine — measured across a greedy cycle, a sampled cycle,
  retire/refill churn, and at least one controller-driven draft-rank
  walk (which may retrace draft-side programs, but never the verify
  window: `make_draft_params` changes factor shapes only on the draft's
  side of the engine)."""
  findings: List[Finding] = []
  infos: List[dict] = []
  for name in config_names:
    name = normalize_config(name)
    if name not in LIFECYCLE_CONFIGS:
      continue
    cfg = configs.get_smoke(name).with_(vocab_size=_VOCAB)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)

    for policy in policies:
      stats, walks = _spec_cycle(cfg, params, policy)
      info = dict(config=name, policy=policy, quant="-",
                  program="lifecycle", check="spec_window_stability",
                  compile_stats=stats, rank_walks=walks)
      infos.append(info)

      def fail(key: str, detail: str) -> None:
        findings.append(Finding(
            check="spec_window_stability", config=name, policy=policy,
            program="lifecycle", key=key, detail=detail))

      if stats["window"] != 1:
        fail(f"window-cache:{stats['window']}",
             f"the batched verify window compiled {stats['window']} "
             f"signatures across greedy+sampled speculative cycles at "
             f"one (bucket, k={_SPEC_K}) — temperature or a draft-rank "
             f"walk leaked into the verify program's jit signature")
      if walks < 1:
        fail("no-rank-walk",
             f"the rank controller never adjusted the draft rank "
             f"(history empty, rank {_SPEC_RANK}) — the window pin was "
             f"not exercised across a draft rebuild and is vacuous")
  return findings, infos


# ---------------------------------------------------------------------------
# speech_fleet_stability
# ---------------------------------------------------------------------------

#: configs whose family serves through the continuous-batching speech fleet
SPEECH_FLEET_CONFIGS = ("deepspeech2-wsj",)

#: mixed, deliberately non-stride-multiple utterance lengths; 3 utterances
#: through 2 slots force a retire -> refill, and the length spread makes
#: the refill admit mid-decode of the surviving stream (staggered masks)
_UTT_LENS = (23, 9, 17)


def _fleet_cycle(cfg, params, policy: str) -> Tuple[dict, dict]:
  """Serve the fleet scenario; returns (uid -> labels, compile_stats)."""
  srv = StreamingSpeechServer(
      cfg, params, batch_size=_BATCH,
      kernel_policy=None if policy == "jnp" else policy)
  rs = np.random.RandomState(0)
  uids = [srv.submit(rs.randn(t, cfg.feat_dim).astype(np.float32))
          for t in _UTT_LENS]
  results = srv.run(chunk_frames=8)
  assert sorted(r.uid for r in results) == sorted(uids)
  return {r.uid: tuple(r.labels) for r in results}, srv.compile_stats()


def check_speech_fleet_stability(
    config_names: Iterable[str],
    policies: Iterable[str]) -> Tuple[List[Finding], List[dict]]:
  """The speech fleet's masked frame step must compile exactly ONE
  signature across admit/chunk/retire/refill with mixed non-stride-
  multiple utterance lengths, each conv stage exactly one signature per
  pow2 window bucket, and the fleet's labels must match a serial
  batch-1 server decoding each utterance alone (continuous batching is
  a scheduling change, not a numerics change)."""
  findings: List[Finding] = []
  infos: List[dict] = []
  for name in config_names:
    name = normalize_config(name)
    if name not in SPEECH_FLEET_CONFIGS:
      continue
    cfg = configs.get_smoke(name)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)

    for policy in policies:
      labels, stats = _fleet_cycle(cfg, params, policy)
      info = dict(config=name, policy=policy, quant="-",
                  program="lifecycle", check="speech_fleet_stability",
                  compile_stats=stats)
      infos.append(info)

      def fail(key: str, detail: str) -> None:
        findings.append(Finding(
            check="speech_fleet_stability", config=name, policy=policy,
            program="lifecycle", key=key, detail=detail))

      if stats["frame_step"] != 1:
        fail(f"frame-step-cache:{stats['frame_step']}",
             f"the masked speech frame step compiled "
             f"{stats['frame_step']} signatures across an "
             f"admit/retire/refill cycle with mixed utterance lengths — "
             f"the fleet's one-signature contract is broken")
      if stats["insert"] > 1:
        fail(f"insert-cache:{stats['insert']}",
             f"slot-insert surgery compiled {stats['insert']} signatures "
             f"— the slot index leaked into the jit signature")
      for stage in ("conv1", "conv2"):
        n_buckets = len(stats[f"{stage}_buckets"])
        if stats[stage] != n_buckets:
          fail(f"{stage}-cache:{stats[stage]}/buckets:{n_buckets}",
               f"{stage} compiled {stats[stage]} signatures but only "
               f"{n_buckets} window buckets ({stats[f'{stage}_buckets']}) "
               f"were streamed: a conv window shape escaped bucketing")

      # serial oracle: each utterance alone through a batch-1 fleet
      srv1 = StreamingSpeechServer(
          cfg, params, batch_size=1,
          kernel_policy=None if policy == "jnp" else policy)
      rs = np.random.RandomState(0)
      for t in _UTT_LENS:
        srv1.submit(rs.randn(t, cfg.feat_dim).astype(np.float32))
      serial = {r.uid: tuple(r.labels) for r in srv1.run(chunk_frames=8)}
      if labels != serial:
        fail("fleet-serial-divergence",
             f"continuous-batched labels differ from serial per-"
             f"utterance decoding: {labels} vs {serial}")
  return findings, infos
