"""Serving: continuous-batching LM decode engine + the paper's streaming
speech path.

LMEngine — continuous batching over a persistent KV / SSM decode state.
The engine owns `batch_size` *slots*, each with its own request lifecycle

    admit -> prefill -> decode -> retire (EOS / token budget / max_len)

and a host-side request queue. Prefill is one jitted `jax.lax.scan` over
prompt positions (bucketed by padded prompt length, so a handful of
programs serve every prompt). Decoding is one masked jitted step for the
whole batch: retired slots keep stepping with clamped positions (their
garbage is overwritten at the next admit), so refilling a slot from the
queue never re-traces. Slot admission uses the ModelApi slot-surgery
helpers (`insert_slot` / `extract_slot` / `reset_slot`): a request is
prefilled into a fresh batch-1 state and spliced into its slot. This is
the paper's §4 regime — batch 1-4 streams amortizing each weight load —
with no slot burning idle once its request finishes.

`max_len` is a hard boundary: prefill rejects prompts that don't fit and
a slot whose cache is full retires with reason "max_len" instead of
wrapping the scatter index and corrupting the cache.

Speculative decoding (`speculate=k`): the engine runs a second, low-rank
model — the stage-2 truncated-SVD factorization of the *same* params
(serving.speculative.make_draft_params, no extra training) — against its
own decode state. Each iteration the draft proposes k tokens
autoregressively and the target verifies all of them in one fused
`ModelApi.decode_window` — per family a true batched window forward (one
causal attention pass over the KV cache, or batched GEMMs with only the
O(1) recurrent carries scanning), so verification reads the weights once
for the whole window instead of k+1 times. At temperature 0,
`accept_longest_prefix` commits the longest agreeing prefix plus one
bonus token (1..k+1 tokens per iteration instead of exactly 1) and
greedy acceptance makes this LOSSLESS: speculative greedy is
token-for-token vanilla greedy. At temperature > 0, `accept_sampled`
runs standard speculative rejection sampling (accept each draft with
prob min(1, p/q), resample the first rejection from the residual), which
keeps every emitted token distributed exactly as vanilla sampling from
the target. Rejected suffixes rewind both models' states with per-family
semantics (ModelApi.decode_state_carry): attention KV rows rewind by
moving the position counter (rows past it are dead until overwritten);
SSM / recurrent carries restore the pre-draft snapshot and replay the
accepted prefix through the masked window program prefill already uses.
An optional `rank_controller` (serving.speculative.RankController) walks
the draft rank online against a target accept-rate band, rebuilding the
draft params in place — the target's verify program never re-jits.

Prefix caching (`prefix_cache=PrefixCache(...)`): admission consults a
radix-trie cache of decode-state snapshots (serving.prefix_cache) keyed
by token prefixes. On a hit the cached snapshot is spliced into a fresh
batch-1 state (`ModelApi.splice_prefix` — eager slot surgery, no new jit
program) and the SAME bucketed fused prefill runs over only the uncached
suffix starting at the cached position; admission then publishes the
full prompt's snapshot back (`publish_on_retire=True` additionally
publishes prompt+generated prefixes at retirement, the multi-turn win).
The spliced state is bit-identical to the cold prefill's state at that
position, so cached-splice greedy serving is token-for-token cold
serving — pinned by tests and the `prefix_splice_stability` audit check.

`cache_dtype` downcasts only the attention KV-cache leaves (see
`models.api.cast_kv_cache`); SSM / recurrent carries stay full precision.

Both engines accept PTQ'd params (repro.quant's QuantizedLinear leaves)
unchanged: under the pallas policy the dispatcher routes those GEMMs to
the int8_gemm kernel consuming the stored scales directly, and under
jnp/no policy the leaf's own w8a8 oracle runs — the same arithmetic, so
quantized serving is policy-invariant token-for-token.

StreamingSpeechServer — the paper's embedded deployment mode: frame-
synchronous DS2 inference. The conv frontend streams over mel chunks
*with receptive-field context carried across chunk boundaries*, so the
streamed CTC labels match the full-utterance forward exactly; each GRU
step is the low-batch recurrent GEMM that kernels/decode_matvec and
kernels/gru_cell target. Under a profiler session it records spans
(`repro.runtime.tracing`): `speech.process_chunk` and `speech.run` at
the top, and inside them `speech.frontend` (the conv feed of every slot)
and `speech.frame_step` (one masked step, counting its `live` slots).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import make_constraint
from repro.kernels.dispatch import resolve_policy
from repro.layers.common import ModelConfig
from repro.models import deepspeech
from repro.models.api import cast_kv_cache, get_model
from repro.runtime import tracing
from repro.serving.prefix_cache import PrefixCache
from repro.serving.speculative import (RankController,
                                       accept_longest_prefix,
                                       accept_sampled, make_draft_params,
                                       merge_rewind)

_INHERIT = object()   # submit(eos_id=...) sentinel: use the engine's eos_id


@dataclasses.dataclass
class GenerationResult:
  tokens: np.ndarray            # (b, steps); rows past their length are 0
  steps: int
  lengths: Optional[np.ndarray] = None   # (b,) generated tokens per row
  # speculative decoding only: accepted draft tokens / drafted tokens
  # over this call (None when the engine decodes vanilla)
  accept_rate: Optional[float] = None


@dataclasses.dataclass
class Request:
  uid: int
  prompt: np.ndarray            # (p,) int32
  max_new_tokens: Optional[int]  # None = until EOS or max_len
  eos_id: Optional[int]


@dataclasses.dataclass
class FinishedRequest:
  uid: int
  prompt: np.ndarray
  tokens: np.ndarray            # generated tokens, prompt excluded
  finish_reason: str            # "eos" | "length" | "max_len"
  # admission-to-first-token wall seconds (prefill latency; queue wait
  # excluded) — the number the prefix cache exists to shrink
  ttft_s: Optional[float] = None


@dataclasses.dataclass
class _SlotState:
  """Host-side ownership record for one decode slot: request lifecycle,
  emitted tokens, and the next token to feed. One object per slot
  (inactive slots hold a blank record) — the single place per-slot state
  hangs off now that features run several models against one decode
  state (the speculative draft here; prefix caches later). Replaces the
  former parallel lists (`_slots` / `_active` / `_next_tok`)."""
  req: Optional[Request] = None
  tokens: list = dataclasses.field(default_factory=list)
  remaining: Optional[int] = None
  active: bool = False
  next_tok: int = 0
  ttft_s: Optional[float] = None


def _next_pow2(n: int) -> int:
  return 1 << max(0, int(n - 1).bit_length())


def _jit_cache_size(fn) -> int:
  """Compiled-signature count of one jit wrapper. Each entry is one
  traced+compiled input signature, so a shape-stable serving loop holds
  this at 1 per program."""
  return int(fn._cache_size())


def make_prefill_program(api, cfg: ModelConfig, cs, policy, axes):
  """Build the fused masked-prefill program (un-jitted).

  Module-level (rather than a closure inside LMEngine.__init__) so the
  engine's two jit variants (`_prefill`, donating `_replay`) and the
  repro.analysis trace harness all audit the SAME program the engine
  serves with, not a lookalike.

  `axes` is `api.decode_state_batch_axes(cfg)` — the per-leaf batch axis
  tree the masked state-select broadcasts over.
  """

  def prefill_prog(params, state, prompts, plens, pos0):
    """Fused prefill: scan over prompt positions inside one program.

    prompts (b, P) padded to the bucket length; plens (b,) true lengths
    (>= 1); pos0 (b,) starting positions. Rows keep stepping past their
    own length with the state select masked back, so one program serves
    every mix of prompt lengths at a bucket size. Returns (last live
    logits per row (b, 1, v) float32, state after plens tokens)."""
    b, P = prompts.shape
    def masked(live, new, old):
      return jax.tree.map(
          lambda n, o, ax: jnp.where(_bcast_mask(live, n.ndim, ax), n, o),
          new, old, axes)
    logits0, state1 = api.decode_step(params, state, prompts[:, 0:1],
                                      pos0, cfg, cs, policy)
    last0 = logits0.astype(jnp.float32)
    def body(carry, t):
      st, last = carry
      tok = jax.lax.dynamic_slice_in_dim(prompts, t, 1, axis=1)
      logits, new_st = api.decode_step(params, st, tok, pos0 + t, cfg,
                                       cs, policy)
      live = t < plens
      st = masked(live, new_st, st)
      last = jnp.where(live[:, None, None], logits.astype(jnp.float32),
                       last)
      return (st, last), None
    (state2, last), _ = jax.lax.scan(body, (state1, last0),
                                     jnp.arange(1, P))
    return last, state2

  return prefill_prog


def _bcast_mask(mask: jax.Array, ndim: int, axis: int) -> jax.Array:
  shape = [1] * ndim
  shape[axis] = mask.shape[0]
  return mask.reshape(shape)


def _host_probs(logits, temperature: float) -> np.ndarray:
  """softmax(logits / temperature) on the host in float64 — the
  acceptance-side view of the distribution `_sample`'s categorical draws
  from (float32 logits over temperature)."""
  x = np.asarray(logits, np.float64) / temperature
  x -= x.max(axis=-1, keepdims=True)
  np.exp(x, out=x)
  x /= x.sum(axis=-1, keepdims=True)
  return x


class LMEngine:

  def __init__(self, model_cfg: ModelConfig, params: Any, *,
               batch_size: int, max_len: int, mesh=None,
               cache_dtype=None, rng=None, kernel_policy=None,
               eos_id: Optional[int] = None, speculate: int = 0,
               draft_params: Any = None, draft_rank: Optional[int] = None,
               rank_controller: Optional[RankController] = None,
               prefix_cache: Optional[PrefixCache] = None,
               publish_on_retire: bool = False):
    self.cfg = model_cfg
    self.params = params
    self.api = get_model(model_cfg)
    if not self.api.decodable:
      raise ValueError(f"{model_cfg.name} has no decode path")
    self.batch = batch_size
    self.max_len = max_len
    self.cache_dtype = cache_dtype
    self.eos_id = eos_id
    if speculate < 0:
      raise ValueError(f"speculate must be >= 0, got {speculate}")
    self.speculate = int(speculate)
    cs = make_constraint(mesh, model_cfg, batch_size, decode=True)
    # the decode-regime KernelPolicy is built HERE, once, like cs: the
    # jitted step closes over it, so "pallas" lowers every eligible GEMM
    # through kernels.dispatch. None keeps the exact jnp program. A
    # speculative engine widens the decode_matvec bound to cover a fused
    # (batch x window)-row verify step (never past the kernel contract).
    policy = resolve_policy(kernel_policy, batch_size,
                            window=self.speculate + 1)
    self.kernel_policy = policy
    self._axes = self.api.decode_state_batch_axes(model_cfg)
    # per-family rewind semantics: carry leaves snapshot/replay, the rest
    # (attention KV, step-invariant memory) rewind positionally for free
    self._carry = self.api.decode_state_carry(model_cfg)
    self._has_carry = any(jax.tree.leaves(self._carry))
    self.state = self._init_state(batch_size)
    self.positions = jnp.zeros((batch_size,), jnp.int32)
    self._rng0 = jax.random.PRNGKey(0) if rng is None else rng
    self.rng = self._rng0

    # the self-speculative draft: same params, matching GEMMs factored
    # at the draft rank, decoding against its own state
    if self.speculate:
      if draft_params is None:
        draft_params = make_draft_params(params, rank=draft_rank)
      self.draft_params = draft_params
      self.draft_state = self._init_state(batch_size)
    else:
      self.draft_params = None
      self.draft_state = None

    # the (optional) online rank controller: walks draft_rank against an
    # accept-rate band, rebuilding the draft in place. Only draft-side
    # programs retrace for the new factor shapes; the target's verify
    # window never re-jits (same params, same signature).
    if rank_controller is not None:
      if not self.speculate:
        raise ValueError("rank_controller requires speculate > 0")
      if draft_rank is None:
        raise ValueError(
            "rank_controller needs a starting draft_rank to walk from "
            "(the explained-variance draft has no single rank)")
    self.rank_controller = rank_controller
    self.draft_rank = draft_rank
    self.rank_history: list = []   # (decode_steps, old_rank, new_rank)
    self._ctrl_step0 = 0
    self._ctrl_drafted0 = 0
    self._ctrl_accepted0 = 0

    # the (optional, shareable) prefix cache: admission splices hits,
    # publishes full prompts, and — opted in — retired prefixes too
    self._cache = prefix_cache
    self.publish_on_retire = publish_on_retire
    self._pending_publish: list = []   # (slot, key tokens, fed length)

    # host-side per-slot lifecycle + the request queue
    self._queue: collections.deque = collections.deque()
    self._slots: list = [_SlotState() for _ in range(batch_size)]
    self._finished: dict = {}
    self._next_uid = 0
    # occupancy accounting for bench_serving: busy slot-steps / slot-steps
    self.decode_steps = 0
    self.busy_slot_steps = 0
    # speculative accounting: accept_rate = accepted / drafted
    self.drafted_tokens = 0
    self.accepted_tokens = 0

    api, cfg = self.api, model_cfg

    def step(params, state, token, positions):
      return api.decode_step(params, state, token, positions, cfg, cs,
                             policy)
    self._step = jax.jit(step, donate_argnums=(1,))
    # carry families snapshot the draft state before drafting; the FIRST
    # draft step reads that snapshot, so it must not donate its buffers
    # (later steps consume disposable intermediates and use _step)
    self._draft_step0 = jax.jit(step) if self._has_carry else self._step

    def window_step(params, state, tokens, positions):
      return api.decode_window(params, state, tokens, positions, cfg, cs,
                               policy)
    # same donation logic: the pre-window snapshot must survive the call
    self._window = jax.jit(
        window_step, donate_argnums=() if self._has_carry else (1,))

    prefill_prog = make_prefill_program(api, cfg, cs, policy, self._axes)
    # no donation: admission prefills from the cached fresh-slot template,
    # which must survive the call
    self._prefill = jax.jit(prefill_prog)
    # the same masked-window program re-advances carries after a
    # speculative rejection (replay of the accepted prefix); its inputs
    # are disposable (post-window KV + pre-draft snapshot), so donate
    self._replay = jax.jit(prefill_prog, donate_argnums=(1,))

    def insert(state, slot_state, slot):
      return api.insert_slot(cfg, state, slot_state, slot)
    self._insert = jax.jit(insert, donate_argnums=(0,))
    # one fresh single-slot decode state, reused as the admission template
    # (for the draft too: factoring weights never changes state shapes)
    self._fresh_slot = self._init_state(1)
    # every (batch, padded prompt length) bucket prefill has compiled
    # for (admission runs at batch 1, the static-batch surface at the
    # engine batch); the retrace-stability audit pins _prefill's cache
    # size to this count. _prefill_calls counts INVOCATIONS per bucket
    # (resets with the other counters) — the splice path shows up here
    # as calls landing in smaller suffix buckets, never as new ones.
    self._prefill_buckets: set = set()
    self._prefill_calls: dict = {}

  def _count_prefill(self, b: int, bucket: int) -> None:
    key = (int(b), int(bucket))
    self._prefill_buckets.add(key)
    self._prefill_calls[key] = self._prefill_calls.get(key, 0) + 1

  def compile_stats(self) -> dict:
    """Compiled-signature counts for every jitted program the engine owns,
    plus per-bucket prefill invocation counts.

    The engine's shape-stability contract — a fixed decode step, bucketed
    prefill — is observable here: after any admit/decode/retire/refill
    sequence (prefix-cache splices included), "step" must sit at exactly
    1, "prefill" at exactly len(prefill_buckets), and the auxiliary
    programs at <= 1 each. A higher count means a signature silently
    re-traced (and recompiled) mid-serve. `repro.analysis`'s
    retrace-stability and prefix-splice-stability checks assert this.

    "prefill_calls" maps "BxL" bucket names to invocation counts since
    init/reset() — benches and the auditor read cache effectiveness
    (splices shift calls into smaller suffix buckets) from this one
    surface next to `cache_stats()`."""
    stats = {
        "step": _jit_cache_size(self._step),
        "prefill": _jit_cache_size(self._prefill),
        "replay": _jit_cache_size(self._replay),
        "window": _jit_cache_size(self._window),
        "insert": _jit_cache_size(self._insert),
        "prefill_buckets": sorted(self._prefill_buckets),
        "prefill_calls": {f"{b}x{p}": n for (b, p), n
                          in sorted(self._prefill_calls.items())},
    }
    # for carry families the draft's first step is a distinct (non-
    # donating) program; otherwise it IS _step and needs no extra key
    if self._draft_step0 is not self._step:
      stats["draft_step0"] = _jit_cache_size(self._draft_step0)
    return stats

  def cache_stats(self) -> dict:
    """Prefix-cache counters (hits / misses / evictions / inserts /
    bytes / hit_rate) — the `PrefixCache.stats()` surface re-exported so
    benches, the serve driver, and the auditor read one place. A
    cacheless engine returns the same shape, zeroed."""
    if self._cache is None:
      return {"hits": 0, "misses": 0, "evictions": 0, "inserts": 0,
              "rejected_oversize": 0, "entries": 0, "bytes": 0,
              "capacity_bytes": 0, "hit_rate": 0.0}
    return self._cache.stats()

  def _init_state(self, batch: int):
    state = self.api.init_decode_state(self.cfg, batch, self.max_len)
    # scope: KV-cache leaves only — SSM/recurrent carries are read-modify-
    # write every step and must keep their working precision
    return cast_kv_cache(state, self.cache_dtype)

  def reset(self) -> None:
    self.state = self._init_state(self.batch)
    if self.speculate:
      self.draft_state = self._init_state(self.batch)
    self.positions = jnp.zeros((self.batch,), jnp.int32)
    self.rng = self._rng0          # seeded sampling restarts with reset
    self._queue.clear()
    self._slots = [_SlotState() for _ in range(self.batch)]
    self._finished = {}
    self.decode_steps = 0
    self.busy_slot_steps = 0
    self.drafted_tokens = 0
    self.accepted_tokens = 0
    self._ctrl_step0 = 0
    self._ctrl_drafted0 = 0
    self._ctrl_accepted0 = 0
    self._prefill_calls = {}
    self._pending_publish = []
    # the prefix cache itself is NOT cleared: it may be shared across
    # engines, and its entries stay valid (snapshots are self-contained)

  # -- request lifecycle ----------------------------------------------------

  def _active_mask(self) -> np.ndarray:
    return np.array([s.active for s in self._slots], bool)

  def _next_tokens(self) -> np.ndarray:
    return np.array([[s.next_tok] for s in self._slots], np.int32)

  @property
  def num_active(self) -> int:
    return sum(s.active for s in self._slots)

  @property
  def accept_rate(self) -> Optional[float]:
    """Accepted draft tokens / drafted tokens since init or reset(), or
    None when nothing has been drafted yet — "no data" and "every draft
    rejected" are different answers, and callers (the serve driver, the
    rank controller, `GenerationResult.accept_rate`) all read None as
    the former. One semantics across every accept-rate surface."""
    return (self.accepted_tokens / self.drafted_tokens
            if self.drafted_tokens else None)

  @property
  def occupancy(self) -> float:
    """Mean fraction of slots doing useful work per engine iteration:
    busy_slot_steps / (decode_steps * batch_size) since init or reset().

    `decode_steps` counts engine ITERATIONS — one masked decode step in
    the vanilla path, one whole draft+verify+commit round in the
    speculative path (which may emit up to k+1 tokens) — and admission
    prefill work is excluded entirely, so this measures slot liveness,
    not tokens/step. 0.0 before any decoding has happened."""
    total = self.decode_steps * self.batch
    return self.busy_slot_steps / total if total else 0.0

  def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
             eos_id=_INHERIT) -> int:
    """Queue one request; returns its uid. `eos_id=None` disables EOS
    retirement for this request (the engine default applies otherwise)."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    if prompt.size == 0:
      raise ValueError("empty prompt")
    if prompt.size > self.max_len:
      raise ValueError(
          f"prompt length {prompt.size} exceeds max_len {self.max_len}")
    if max_new_tokens is not None and max_new_tokens < 1:
      raise ValueError("max_new_tokens must be >= 1")
    uid = self._next_uid
    self._next_uid += 1
    eos = self.eos_id if eos_id is _INHERIT else eos_id
    self._queue.append(Request(uid=uid, prompt=prompt,
                               max_new_tokens=max_new_tokens, eos_id=eos))
    return uid

  def _retire(self, slot: int, reason: str) -> None:
    s = self._slots[slot]
    self._finished[s.req.uid] = FinishedRequest(
        uid=s.req.uid, prompt=s.req.prompt,
        tokens=np.asarray(s.tokens, np.int32), finish_reason=reason,
        ttft_s=s.ttft_s)
    if self._cache is not None and self.publish_on_retire:
      # the retired conversation's fed prefix (prompt + every generated
      # token except the final, never-fed one) is a cacheable entry —
      # the multi-turn continuation hit. Deferred: the batch state may
      # still be mid-update here (speculative rewind pending), so the
      # snapshot is taken at the caller's flush point.
      fed = s.req.prompt.size + len(s.tokens) - 1
      if fed > 0:
        key = np.concatenate(
            [s.req.prompt, np.asarray(s.tokens[:-1], np.int32)])
        self._pending_publish.append((slot, key, fed))
    self._slots[slot] = _SlotState()
    # no state scrub here: the slot keeps stepping masked (positions
    # clamped to 0) and the next admit splices a fully fresh prefilled
    # state over every row of the slot

  def _flush_retire_publish(self, *, invalid_slots=()) -> None:
    """Publish the prefixes queued by `_retire`, dropping only the slots
    named in `invalid_slots`. Validity is PER SLOT: the speculative
    full-accept fast path skips the masked replay, which leaves a
    partially-accepted retired slot's carries at post-window values (not
    the committed prefix) — those publishes must drop — while a slot
    that retired having accepted its whole window holds carries that ARE
    the committed values (the window state at exactly `fed` tokens), so
    its publish is good. Vanilla decode and the replay path pass nothing
    and publish everything."""
    for slot, key, fed in self._pending_publish:
      if slot in invalid_slots:
        continue
      snap = self.api.slot_snapshot(self.cfg, self.state, slot, fed)
      # retire publishes target-only: the draft re-prefills on a hit
      self._cache.insert(key, (snap, None))
    self._pending_publish.clear()

  def _record_token(self, slot: int, tok: int, pos: int) -> bool:
    """Append a sampled token; retire the slot if the request is done.
    `pos` is the slot's cache write count. Returns True while the slot
    stays active."""
    s = self._slots[slot]
    s.tokens.append(tok)
    if s.remaining is not None:
      s.remaining -= 1
    if s.req.eos_id is not None and tok == s.req.eos_id:
      self._retire(slot, "eos")
      return False
    if s.remaining == 0:
      self._retire(slot, "length")
      return False
    if pos >= self.max_len:
      # cache full: one more step would scatter past max_len and corrupt
      # the KV cache — retire instead (the hard boundary)
      self._retire(slot, "max_len")
      return False
    return True

  def _pad_prefill(self, tokens: np.ndarray, start: int):
    """Bucket-pad a token run fed at positions [start, start+len) into
    the fused-prefill operand triple (toks, lens, pos0)."""
    n = tokens.size
    bucket = min(max(self.max_len, 1), _next_pow2(n))
    self._count_prefill(1, bucket)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = tokens
    return (jnp.asarray(padded), jnp.asarray([n], jnp.int32),
            jnp.full((1,), start, jnp.int32))

  def _admit(self, req: Request, slot: int, temperature: float) -> None:
    """Prefill `req` into a fresh batch-1 state and splice it into `slot`.

    With a prefix cache, admission first looks up the longest cached
    prefix (capped at plen - 1: the suffix prefill must feed at least
    one token so there are fresh last-position logits to sample from),
    splices its snapshot into the fresh-slot template — eager slot
    surgery, bit-identical to the cold state at that position — and runs
    the SAME bucketed fused prefill over only the suffix, starting at
    the cached position. When the trie has observed a deeper shared
    prefix than any entry covers (a fork between sibling prompts), the
    suffix prefill is split at the fork and the intermediate state
    published, so the next sibling splices from the fork instead of
    re-prefilling the shared template. The full prompt's snapshot is
    then published too, so every admission deepens the cache.

    A speculative engine prefills the draft's state alongside: both
    models must have consumed the prompt before drafting can start. The
    draft splices too when the hit carries a draft snapshot; otherwise
    it cold-prefills the whole prompt (states are independent — a
    draft-side cold start costs accept-rate nothing).
    """
    t_admit = time.perf_counter()
    plen = req.prompt.size
    cached, draft_snap = 0, None
    start = self._fresh_slot
    publish_fork = 0
    if self._cache is not None and plen > 1:
      cached, payload = self._cache.lookup(req.prompt[:plen - 1])
      if cached:
        target_snap, draft_snap = payload
        start = self.api.splice_prefix(self.cfg, self._fresh_slot,
                                       target_snap)
      # fork materialization: entries live at whole inserted prompts, so
      # two prompts sharing a prefix but diverging before any entry end
      # would never hit each other. The trie has *observed* their common
      # prefix even without an entry there — when that uncovered depth is
      # deep enough to be a real template (fork_min_tokens), split the
      # prefill at the fork and publish the intermediate state, so the
      # third sibling onward splices it. Carries are only valid at exact
      # lengths, which is why the fork state must come from a prefill
      # that stops there rather than a post-hoc slice.
      fork = self._cache.common_prefix_len(req.prompt[:plen - 1])
      if fork - cached >= self._cache.fork_min_tokens:
        publish_fork = fork
    # the draft snapshot (if any) is valid at the pre-fork depth only
    draft_from = cached
    if publish_fork:
      ftoks, fplens, fpos0 = self._pad_prefill(
          req.prompt[cached:publish_fork], cached)
      _, start = self._prefill(self.params, start, ftoks, fplens, fpos0)
      self._cache.insert(
          req.prompt[:publish_fork],
          (self.api.prefix_view(self.cfg, start, publish_fork), None))
      cached = publish_fork
    toks, plens, pos0 = self._pad_prefill(req.prompt[cached:], cached)
    sl = jnp.asarray(slot, jnp.int32)
    last, slot_state = self._prefill(self.params, start, toks, plens,
                                     pos0)
    self.state = self._insert(self.state, slot_state, sl)
    self.positions = self.positions.at[slot].set(plen)
    self._slots[slot] = _SlotState(req=req, remaining=req.max_new_tokens,
                                   active=True)
    # the first token always comes from the TARGET's prefill logits —
    # identical to vanilla admission, the draft only ever proposes
    tok = int(np.asarray(self._sample(last, temperature))[0, 0])
    self._slots[slot].ttft_s = time.perf_counter() - t_admit
    draft_slot = None
    if self._record_token(slot, tok, plen):
      self._slots[slot].next_tok = tok
      if self.speculate:
        # only slots that survive admission ever draft — a request that
        # retires here (budget 1, EOS in the prefill logits, full
        # cache) would waste the whole draft prefill
        if draft_snap is not None:
          dstart = self.api.splice_prefix(self.cfg, self._fresh_slot,
                                          draft_snap)
          dtoks, dplens, dpos0 = self._pad_prefill(
              req.prompt[draft_from:], draft_from)
          _, draft_slot = self._prefill(self.draft_params, dstart, dtoks,
                                        dplens, dpos0)
        else:
          ftoks, fplens, fpos0 = self._pad_prefill(req.prompt, 0)
          _, draft_slot = self._prefill(self.draft_params,
                                        self._fresh_slot, ftoks, fplens,
                                        fpos0)
        self.draft_state = self._insert(self.draft_state, draft_slot, sl)
    if self._cache is not None:
      # publish the full prompt (admission cost already sunk); carries
      # in slot_state are exactly at plen, so the snapshot is valid
      snap = self.api.prefix_view(self.cfg, slot_state, plen)
      dsnap = (self.api.prefix_view(self.cfg, draft_slot, plen)
               if draft_slot is not None else None)
      self._cache.insert(req.prompt, (snap, dsnap))
    # a request that retired during admission queued its publish; the
    # batch state already holds this slot's rows, so flush is safe here
    self._flush_retire_publish()

  def _admit_from_queue(self, temperature: float) -> None:
    slot = 0
    while self._queue and slot < self.batch:
      if self._slots[slot].active:
        slot += 1
        continue
      # a request may finish during admission (EOS in the prefill logits,
      # budget 1, or a full cache) — then the slot is still free
      self._admit(self._queue.popleft(), slot, temperature)

  def _decode_all(self, temperature: float) -> None:
    """One masked decode step for every slot. Inactive slots step with
    positions clamped to 0 and token 0; their state rows are garbage until
    the next admit overwrites them, which keeps the step program fixed."""
    active_np = self._active_mask()
    active = jnp.asarray(active_np)
    safe_pos = jnp.where(active, self.positions, 0)
    logits, self.state = self._step(self.params, self.state,
                                    jnp.asarray(self._next_tokens()),
                                    safe_pos)
    self.positions = jnp.where(active, self.positions + 1, self.positions)
    self.decode_steps += 1
    self.busy_slot_steps += int(active_np.sum())
    toks = np.asarray(self._sample(logits, temperature))
    pos = np.asarray(self.positions)        # one host sync per step
    for i in range(self.batch):
      if self._slots[i].active and self._record_token(i, int(toks[i, 0]),
                                                      int(pos[i])):
        self._slots[i].next_tok = int(toks[i, 0])
    # vanilla path: the stepped state is final — retired prefixes publish
    self._flush_retire_publish()

  def _decode_all_speculative(self, temperature: float) -> None:
    """One speculative iteration for every slot: draft k, verify k+1 in
    one fused window, commit the accepted prefix + bonus, rewind the
    rejected suffix. Temperature 0 accepts greedily (lossless:
    token-for-token vanilla greedy); temperature > 0 rejection-samples
    against the draft distribution (accept_sampled — the emitted tokens
    are distributed exactly as vanilla sampling from the target).

    Window layout per slot: inputs [t0, d_1..d_k] fed at positions
    p..p+k (t0 = the committed-but-unfed token) produce target
    distributions p_1..p_{k+1}; after accepting `a` drafts the slot
    commits d_1..d_a plus one more token (greedy: the target argmax
    g_{a+1}; sampled: the residual resample or the bonus draw) and its
    position moves to p+a+1. Writes past max_len fall off the cache
    (JAX scatter drops out-of-bounds updates) and the commit loop
    retires the slot at the boundary first, so the hard max_len
    contract survives speculation."""
    k = self.speculate
    sampled = temperature > 0.0
    active_np = self._active_mask()
    pos_np = np.asarray(self.positions)
    active = jnp.asarray(active_np)
    pos0 = jnp.where(active, self.positions, 0)

    # -- draft: k autoregressive proposals against the draft's own state
    if self._has_carry:
      draft_snap = self.draft_state    # pre-draft carry snapshot (refs)
    cur = jnp.asarray(self._next_tokens())
    cols = [cur]
    draft_lgs = []          # sampled path: q_j, the draft distributions
    for j in range(k):
      # step 0 reads the pre-draft snapshot (must survive — no
      # donation); later steps consume disposable intermediates
      step_fn = self._draft_step0 if j == 0 else self._step
      lg, self.draft_state = step_fn(self.draft_params, self.draft_state,
                                     cur, pos0 + j)
      cur = self._sample(lg, temperature)
      cols.append(cur)
      if sampled:
        draft_lgs.append(lg[:, -1:])
    if not self._has_carry:
      # pure-KV families: one extra draft step consumes d_k so a fully
      # accepted window leaves the draft cache complete through p+k
      # (carry families cover this with the replay below instead)
      _, self.draft_state = self._step(self.draft_params,
                                       self.draft_state, cur, pos0 + k)
    window = jnp.concatenate(cols, axis=1)          # (b, k+1)

    # -- verify: all k+1 positions in one fused window step
    if self._has_carry:
      snap = self.state                # pre-window carry snapshot (refs)
    logits_w, self.state = self._window(self.params, self.state, window,
                                        pos0)
    window_np = np.asarray(window)
    if sampled:
      # rejection sampling needs the exact distributions both models
      # sample from: softmax of the float32 logits at the temperature
      q = _host_probs(jnp.concatenate(draft_lgs, axis=1), temperature)
      p = _host_probs(logits_w, temperature)
      if not active_np.all():
        # inactive slots step with garbage state rows; their (discarded)
        # acceptance math still must not see non-finite probabilities
        q[~active_np] = 1.0 / q.shape[-1]
        p[~active_np] = 1.0 / p.shape[-1]
      accept, out_toks, out_len = accept_sampled(window_np[:, 1:], q, p,
                                                 self._host_rng())
    else:
      target = np.asarray(jnp.argmax(logits_w, axis=-1), np.int32)
      accept, out_toks, out_len = accept_longest_prefix(window_np[:, 1:],
                                                        target)
    self.decode_steps += 1
    self.busy_slot_steps += int(active_np.sum())

    # -- commit: accepted prefix + bonus, via the vanilla retirement rules
    commit = np.ones((self.batch,), np.int32)  # window tokens consumed
    for i in range(self.batch):
      s = self._slots[i]
      if not s.active:
        continue
      self.drafted_tokens += k
      alive = True
      for j in range(int(out_len[i])):
        commit[i] = j + 1
        alive = self._record_token(i, int(out_toks[i, j]),
                                   int(pos_np[i]) + j + 1)
        if not alive:
          break                      # EOS / budget / max_len mid-window
      if alive:
        s.next_tok = int(out_toks[i, int(out_len[i]) - 1])   # the bonus
      # realized acceptance only: drafts the window agreed on but a
      # mid-window retirement never emitted don't inflate the rate
      self.accepted_tokens += min(int(accept[i]), int(commit[i]))
    commit_j = jnp.asarray(commit)
    self.positions = jnp.where(active, self.positions + commit_j,
                               self.positions)

    # -- rewind the rejected suffix (per-family, see decode_state_carry):
    # KV rows past the new position are dead until overwritten; carries
    # restore the snapshot and replay the accepted prefix masked. Slots
    # retired above tolerate garbage (the next admit splices a fully
    # fresh state), so only surviving slots constrain the rewind. The
    # path choice below depends on the accept pattern; that is sound
    # because every path computes the same committed state bit-for-bit
    # (window scan == masked replay scan == lone steps — the same
    # cross-program invariant losslessness rests on).
    replayed = False
    if self._has_carry:
      live = [i for i in range(self.batch) if self._slots[i].active]
      if live and any(commit[i] != k + 1 for i in live):
        replayed = True
        # a surviving slot rejected part of its window: carries come
        # from the snapshots, replayed through the accepted prefix
        restored = merge_rewind(self.state, snap, self._carry)
        _, self.state = self._replay(self.params, restored, window,
                                     commit_j, pos0)
        restored = merge_rewind(self.draft_state, draft_snap, self._carry)
        _, self.draft_state = self._replay(self.draft_params, restored,
                                           window, commit_j, pos0)
      elif live:
        # every surviving slot accepted its whole window: the target's
        # post-window carries already ARE the committed carries, and
        # the draft (one token behind — it never consumed d_k) catches
        # up with a single step instead of a (k+1)-position replay
        _, self.draft_state = self._step(self.draft_params,
                                         self.draft_state, cur, pos0 + k)
    # retired prefixes: a slot's carries are the committed values if this
    # family has none (KV rows [0, fed) are always exact), if the masked
    # replay above re-advanced every row to its own commit count, or if
    # the slot accepted its WHOLE window (post-window carries == state at
    # exactly `fed` tokens). Only partially-accepted retired slots under
    # the full-accept fast path hold post-window garbage — drop exactly
    # those, per slot, instead of the whole batch's publishes.
    invalid = ()
    if self._has_carry and not replayed:
      invalid = {s for (s, _, _) in self._pending_publish
                 if int(commit[s]) != k + 1}
    self._flush_retire_publish(invalid_slots=invalid)
    self._maybe_adapt_rank()

  def _host_rng(self) -> np.random.Generator:
    """One host-side RNG per speculative acceptance round, forked from
    the engine's JAX key chain — run(rng=...) reproduces the rejection
    draws exactly like it reproduces the categorical samples."""
    self.rng, k = jax.random.split(self.rng)
    seed = np.asarray(jax.random.randint(k, (2,), 0, np.iinfo(np.int32).max))
    return np.random.default_rng(seed.tolist())

  def _maybe_adapt_rank(self) -> None:
    """Rank-controller tick: every `interval` engine iterations, measure
    the window's accept rate and apply the controller's proposal by
    rebuilding the draft params at the new rank. The draft's decode
    state carries over (factoring weights never changes state shapes) —
    stale draft-side caches cost accept rate for a few iterations, never
    correctness (the target verifies everything). Draft-side programs
    retrace for the new factor shapes; the verify window does not."""
    rc = self.rank_controller
    if rc is None or self.decode_steps - self._ctrl_step0 < rc.interval:
      return
    d = self.drafted_tokens - self._ctrl_drafted0
    a = self.accepted_tokens - self._ctrl_accepted0
    new = rc.propose(self.draft_rank, a / d if d else None)
    if new != self.draft_rank:
      self.rank_history.append((self.decode_steps, self.draft_rank, new))
      self.draft_rank = new
      self.draft_params = make_draft_params(self.params, rank=new)
    self._ctrl_step0 = self.decode_steps
    self._ctrl_drafted0 = self.drafted_tokens
    self._ctrl_accepted0 = self.accepted_tokens

  def run(self, *, temperature: float = 0.0, rng=None) -> list:
    """Drain the queue: admit, decode, retire, refill until idle. Returns
    the requests finished since the last call, in submission order.
    `rng` seeds sampled (temperature > 0) decoding for this call — pass
    the same key to reproduce a run exactly (speculative rejection
    sampling forks its host RNG from the same chain)."""
    if rng is not None:
      self.rng = rng
    while self._queue or self.num_active:
      self._admit_from_queue(temperature)
      if self.num_active:
        if self.speculate:
          self._decode_all_speculative(temperature)
        else:
          self._decode_all(temperature)
    out = [self._finished[uid] for uid in sorted(self._finished)]
    self._finished = {}
    return out

  # -- static-batch compatibility surface -----------------------------------

  def prefill(self, prompts: np.ndarray) -> jax.Array:
    """Feed prompts (b, p) through the fused prefill scan; returns last
    logits (b, 1, v). Static-batch surface: b must equal batch_size."""
    prompts = np.asarray(prompts)
    b, p = prompts.shape
    if b != self.batch:
      raise ValueError(f"prefill batch {b} != engine batch {self.batch}")
    if p == 0:
      raise ValueError("empty prompts")
    start = np.asarray(self.positions)
    if int(start.max()) + p > self.max_len:
      raise ValueError(
          f"prefill would pass max_len={self.max_len} "
          f"(start {int(start.max())} + prompt {p})")
    bucket = min(max(self.max_len, 1), _next_pow2(p))
    self._count_prefill(b, bucket)
    padded = np.zeros((b, bucket), np.int32)
    padded[:, :p] = prompts
    logits, self.state = self._prefill(
        self.params, self.state, jnp.asarray(padded),
        jnp.full((b,), p, jnp.int32), self.positions)
    self.positions = self.positions + p
    return logits

  def generate(self, prompts: np.ndarray, *, steps: int,
               temperature: float = 0.0, rng=None) -> GenerationResult:
    """Static-batch wrapper over the continuous engine: every row becomes
    a request with a `steps` token budget and no EOS exit (legacy
    semantics). Rows retired early at the max_len boundary come back
    shorter; see `lengths`. Accepts more rows than slots — extras queue.
    A speculative engine reports the measured accept rate of the call."""
    prompts = np.asarray(prompts)
    drafted0, accepted0 = self.drafted_tokens, self.accepted_tokens
    uids = [self.submit(row, max_new_tokens=steps, eos_id=None)
            for row in prompts]
    by_uid = {f.uid: f for f in self.run(temperature=temperature, rng=rng)}
    tokens = np.zeros((len(uids), steps), np.int32)
    lengths = np.zeros((len(uids),), np.int32)
    for r, uid in enumerate(uids):
      t = by_uid[uid].tokens
      tokens[r, :t.size] = t
      lengths[r] = t.size
    drafted = self.drafted_tokens - drafted0
    rate = ((self.accepted_tokens - accepted0) / drafted
            if self.speculate and drafted else None)
    return GenerationResult(tokens=tokens, steps=steps, lengths=lengths,
                            accept_rate=rate)

  def _sample(self, logits: jax.Array, temperature: float) -> jax.Array:
    lg = logits[:, -1].astype(jnp.float32)
    if temperature <= 0.0:
      return jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
    self.rng, k = jax.random.split(self.rng)
    return jax.random.categorical(
        k, lg / temperature, axis=-1)[:, None].astype(jnp.int32)


# ----------------------------------------------------------------------------
# Streaming speech.
# ----------------------------------------------------------------------------


def _same_pad(size: int, kernel: int, stride: int) -> tuple[int, int]:
  """XLA/TF SAME padding split for a fixed, fully visible axis length."""
  out = -(-size // stride)
  total = max((out - 1) * stride + kernel - size, 0)
  return total // 2, total - total // 2


class _ConvStream:
  """Host bookkeeping of one strided-conv stage streamed over time.

  Implements the `deepspeech.conv_time_pads` convention: a fixed left
  pad of (k - s) // 2 zeros is materialized once at stream start, pushed
  frames are buffered, and output frame j is ready as soon as its
  receptive field [j*s - pl, j*s - pl + k) is complete. `flush` computes
  the right pad *from the actual frame count* — exactly the zeros needed
  to complete ceil(n_in / s) output frames — so chunked emission equals
  the full-utterance conv frame-for-frame for ANY utterance length, not
  just stride multiples.

  The stream runs no conv itself: `push` and `flush` hand back the input
  window of the output frames that just became complete, (m - 1) * s + k
  frames for m outputs, and the server convolves every stream's window
  of a stage in one gathered call.
  """

  def __init__(self, kernel: int, stride: int):
    self.k, self.s = kernel, stride
    self.pad_l = (kernel - stride) // 2
    self.buf: Optional[np.ndarray] = None
    self.n_in = 0                # frames received, padding excluded
    self.n_out = 0               # frames emitted so far
    self.flushed = False

  def _zeros(self, like: np.ndarray, t: int) -> np.ndarray:
    return np.zeros((like.shape[0], t) + like.shape[2:], like.dtype)

  def _append(self, x) -> None:
    x = np.asarray(x)
    if x.shape[1] == 0:
      return
    if self.buf is None:
      self.buf = np.concatenate([self._zeros(x, self.pad_l), x], axis=1)
    else:
      self.buf = np.concatenate([self.buf, x.astype(self.buf.dtype)],
                                axis=1)
    self.n_in += x.shape[1]

  def _emit(self) -> Optional[np.ndarray]:
    n = self.buf.shape[1]
    m = (n - self.k) // self.s + 1 if n >= self.k else 0
    if m <= 0:
      return None
    window = self.buf[:, :(m - 1) * self.s + self.k]
    self.buf = self.buf[:, m * self.s:]
    self.n_out += m
    return window

  def push(self, x) -> Optional[np.ndarray]:
    """Buffer frames x (1, t, ...); the window of the outputs now
    complete, or None."""
    if self.flushed:
      raise RuntimeError("conv stream already flushed; reset() first")
    self._append(x)
    return None if self.buf is None else self._emit()

  def flush(self, tail=None) -> Optional[np.ndarray]:
    """End of stream: buffer the last frames `tail`, if any, right-pad,
    and return the window of every output not yet emitted, or None."""
    # idempotent: re-flushing must not re-pad the residual buffer and
    # complete a fake window
    if self.flushed:
      return None
    self.flushed = True
    if tail is not None:
      self._append(tail)
    if self.buf is None:
      return None
    out_total = -(-self.n_in // self.s)
    pad_r = (out_total - 1) * self.s + self.k - self.pad_l - self.n_in
    if pad_r > 0:
      self.buf = np.concatenate(
          [self.buf, self._zeros(self.buf, pad_r)], axis=1)
    return self._emit()

  def reset(self) -> None:
    self.buf = None
    self.n_in = 0
    self.n_out = 0
    self.flushed = False


@dataclasses.dataclass
class SpeechResult:
  """One retired utterance from the speech fleet."""
  uid: int
  labels: list                  # collapsed greedy-CTC label sequence
  frames: int                   # raw mel frames consumed
  log_probs: np.ndarray         # (t', vocab) per-frame CTC log-probs


class _SpeechSlot:
  """Host-side ownership record for one speech stream: the per-stream
  conv receptive-field context (`s1`/`s2`), the per-stream CTC collapse
  state (`prev` — reset to -1 on admit, never shared across slots), the
  post-frontend frames awaiting a decode step (`pending`), and the
  labels emitted so far. The speech sibling of `_SlotState`."""

  __slots__ = ("uid", "feats", "fed", "labels", "prev", "s1", "s2",
               "pending", "flushed", "log_probs")

  def __init__(self, uid, feats, s1, s2):
    self.uid = uid
    self.feats = feats            # (t, feat_dim) np, or None (lockstep)
    self.fed = 0                  # raw frames pushed into s1 so far
    self.labels: list = []
    self.prev = -1                # per-stream collapse state
    self.s1, self.s2 = s1, s2
    self.pending = collections.deque()   # (gru_in,) frames to decode
    self.flushed = False          # frontend drained (right edge padded)
    self.log_probs: list = []     # (vocab,) per decoded frame

  @property
  def done(self) -> bool:
    return self.flushed and not self.pending


class StreamingSpeechServer:
  """Continuous-batching frame-synchronous DS2 fleet (paper §4 regime).

  Two serving surfaces over the same masked decode program:

  * **Fleet** (`submit` + `run`): an admit/chunk/retire lifecycle over
    `batch_size` slots. Each admitted utterance owns a `_SpeechSlot`
    with its own pair of `_ConvStream` frontends (receptive-field
    context never crosses streams) and its own CTC collapse state
    (reset on admit). Every decode step is ONE masked fixed-shape
    `frame_step` over all slots — inactive or exhausted slots keep
    their state via the mask — so thousands of utterances of mixed,
    arbitrary (non-stride-multiple) lengths share one jit signature
    across retire -> refill, exactly like `LMEngine`'s decode step.
    Slot admission zeroes the slot's GRU rows through the jitted
    `ModelApi.insert_slot` surgery (traced slot index: one program).
    The frontend is gathered the same way: each conv stage runs as one
    `batch_size`-row call per iteration over every slot's ready window,
    flush windows included, never once per slot.

  * **Lockstep** (`process_chunk` / `flush`): the legacy single-group
    API — all `batch_size` streams advance through the same chunk
    boundaries. Kept for frame-synchronous duplex use; internally it is
    the fleet path with every slot live.

  Chunked emission is exactly the full-utterance `deepspeech.forward`
  for ANY utterance length: the conv frontend follows the fixed-left-pad
  convention of `deepspeech.conv_time_pads`, and `_ConvStream.flush`
  right-pads to complete ceil(t / stride) frames instead of asserting
  stride alignment.
  """

  def __init__(self, model_cfg: ModelConfig, params: Any, *,
               batch_size: int = 1, kernel_policy=None):
    self.cfg = model_cfg
    self.params = params
    self.batch = batch_size
    # frame-synchronous GRU steps are the paper's decode regime; a
    # "pallas" policy routes them through gru_cell / decode_matvec
    policy = resolve_policy(kernel_policy, batch_size)
    self.kernel_policy = policy
    self._api = get_model(model_cfg)
    self.state = deepspeech.init_decode_state(model_cfg, batch_size)

    def frame_step(params, state, x_t, active):
      log_probs, new = deepspeech.decode_step(params, state, x_t,
                                              model_cfg, policy=policy)
      new = jax.tree.map(
          lambda n, o: jnp.where(_bcast_mask(active, n.ndim, 0), n, o),
          new, state)
      return log_probs, new
    self._frame_step = jax.jit(frame_step, donate_argnums=(1,))

    def insert(state, slot_state, slot):
      return self._api.insert_slot(model_cfg, state, slot_state, slot)
    self._insert = jax.jit(insert, donate_argnums=(0,))
    self._fresh_slot = deepspeech.init_decode_state(model_cfg, 1)

    cfg = model_cfg
    # geometry comes from the conv weights themselves (one source of
    # truth with deepspeech.init_model) + the shared stride constants
    k1t, k1f = params["conv1"].shape[:2]
    k2t, k2f = params["conv2"].shape[:2]
    s1t, sf = deepspeech.CONV1_TIME_STRIDE, deepspeech.CONV_FREQ_STRIDE
    f1l, f1r = _same_pad(cfg.feat_dim, k1f, sf)
    f2l, f2r = _same_pad(-(-cfg.feat_dim // sf), k2f, sf)
    self._geom = (k1t, s1t, k2t, cfg.time_stride)
    freq_after = ((cfg.feat_dim + 1) // 2 + 1) // 2
    self._gru_in = freq_after * cfg.conv_channels

    def conv1(params, x):                       # (b, t, f) raw mel
      x = jax.lax.conv_general_dilated(
          x[..., None].astype(cfg.dtype), params["conv1"],
          window_strides=(s1t, sf), padding=((0, 0), (f1l, f1r)),
          dimension_numbers=("NHWC", "HWIO", "NHWC"))
      return jax.nn.relu(x.astype(jnp.float32)).astype(cfg.dtype)

    def conv2(params, x):                       # (b, t, f', ch)
      x = jax.lax.conv_general_dilated(
          x, params["conv2"], window_strides=(cfg.time_stride, sf),
          padding=((0, 0), (f2l, f2r)),
          dimension_numbers=("NHWC", "HWIO", "NHWC"))
      x = jax.nn.relu(x.astype(jnp.float32)).astype(cfg.dtype)
      b, t, f, c = x.shape
      return x.reshape(b, t, f * c)

    self._conv1 = jax.jit(conv1)
    self._conv2 = jax.jit(conv2)
    self._buckets1: set = set()
    self._buckets2: set = set()

    self._slots: list = [None] * batch_size
    self._queue: collections.deque = collections.deque()
    self._next_uid = 0
    self._mode: Optional[str] = None     # None | "lockstep" | "fleet"
    self._finished = False               # lockstep: utterance finalized
    self.decode_steps = 0                # masked frame_step invocations
    self.busy_steps = 0                  # live (slot, frame) pairs stepped

  # -- shared machinery -----------------------------------------------------

  def _gathered(self, stage: int, windows: dict) -> dict:
    """Run conv stage `stage` (0 or 1), VALID in time, over every slot's
    ready window {slot index: (1, t, ...) or None} in ONE call. Row i
    holds slot i's window, zero-padded on the right to the pow2 time
    bucket of the longest window, or of 2 * kernel if that is longer;
    rows without a window are zeros and their outputs are dropped. The
    batch stays `batch_size`, so a stage keeps one jit signature per
    bucket; the pad only creates output frames past each row's real
    ones, which are sliced off (VALID conv is local in time and rows
    never mix). The floor: which slots' windows meet in a call, and so
    the longest, depends on the slot mix, and short windows (every flush
    window, and all of them at chunks up to about `kernel` frames) must
    not reach a bucket that a warm-up with another mix never compiled.
    Returns {slot index: (1, m, ...)}."""
    fn, buckets = ((self._conv1, self._buckets1),
                   (self._conv2, self._buckets2))[stage]
    kernel, stride = self._geom[2 * stage:2 * stage + 2]
    windows = {i: w for i, w in windows.items() if w is not None}
    if not windows:
      return {}
    tp = _next_pow2(max(max(w.shape[1] for w in windows.values()),
                        2 * kernel))
    like = next(iter(windows.values()))
    x = np.zeros((self.batch, tp) + like.shape[2:], like.dtype)
    for i, w in windows.items():
      x[i, :w.shape[1]] = w[0]
    buckets.add(tp)
    y = np.asarray(fn(self.params, jnp.asarray(x)))
    return {i: y[i:i + 1, :(w.shape[1] - kernel) // stride + 1]
            for i, w in windows.items()}

  def _make_streams(self):
    return (_ConvStream(self._geom[0], self._geom[1]),
            _ConvStream(self._geom[2], self._geom[3]))

  def _feed(self, chunks: dict, finals) -> None:
    """Push raw mel chunks {slot index: (1, t, f)} through their slots'
    conv streams, flushing the streams of the slots in `finals` (their
    last chunk, if any, goes into the flush): ONE gathered conv call per
    stage, whatever the number of slots and of streams that end.
    Receptive-field context stays in each slot's own streams. Queues
    every completed post-frontend frame for decoding."""
    slots, finals = self._slots, set(finals)

    def ready(i, stream, x):
      return stream.flush(x) if i in finals else stream.push(x)
    y1 = self._gathered(0, {i: ready(i, slots[i].s1, chunks.get(i))
                            for i in finals | set(chunks)})
    out = self._gathered(1, {i: ready(i, slots[i].s2, y1.get(i))
                             for i in finals | set(y1)})
    for i in finals:
      slots[i].flushed = True
    for i, o in out.items():
      slots[i].pending.extend(o[0])

  def _decode_pending(self) -> list:
    """Masked frame steps until no live slot has a pending frame.

    One fixed-shape `frame_step` per frame position: slots without a
    frame at this position are masked out of the state update and their
    (garbage) logits ignored — the speech analogue of LMEngine's masked
    decode. Greedy-CTC collapse runs per live slot against ITS OWN
    `prev`. Returns per-slot newly emitted labels (lockstep API)."""
    emitted = [[] for _ in range(self.batch)]
    dtype = np.dtype(self.cfg.dtype)
    while True:
      live = [i for i, s in enumerate(self._slots)
              if s is not None and s.pending]
      if not live:
        return emitted
      with tracing.span("speech.frame_step", live=len(live)):
        x = np.zeros((self.batch, self._gru_in), dtype)
        mask = np.zeros((self.batch,), bool)
        for i in live:
          x[i] = self._slots[i].pending.popleft()
          mask[i] = True
        log_probs, self.state = self._frame_step(
            self.params, self.state, jnp.asarray(x), jnp.asarray(mask))
        rows = np.asarray(log_probs)
      best = rows.argmax(axis=-1)
      for i in live:
        slot, b = self._slots[i], int(best[i])
        slot.log_probs.append(rows[i])
        if b != 0 and b != slot.prev:
          slot.labels.append(b)
          emitted[i].append(b)
        slot.prev = b
      self.decode_steps += 1
      self.busy_steps += len(live)

  # -- fleet lifecycle ------------------------------------------------------

  def submit(self, feats: np.ndarray) -> int:
    """Queue one utterance (t, feat_dim) of ANY length; returns its uid."""
    if self._mode == "lockstep":
      raise RuntimeError("server is mid-lockstep-utterance; reset() first")
    self._mode = "fleet"
    feats = np.asarray(feats)
    if feats.ndim != 2 or feats.shape[-1] != self.cfg.feat_dim:
      raise ValueError(f"expected (t, {self.cfg.feat_dim}) mel features, "
                       f"got {feats.shape}")
    uid = self._next_uid
    self._next_uid += 1
    self._queue.append((uid, feats))
    return uid

  def _admit(self) -> None:
    for i in range(self.batch):
      if self._slots[i] is None and self._queue:
        uid, feats = self._queue.popleft()
        s1, s2 = self._make_streams()
        slot = _SpeechSlot(uid, feats, s1, s2)
        self._slots[i] = slot
        # zero the slot's GRU rows (jitted surgery, traced slot index:
        # one program for every slot) and reset ITS collapse state —
        # a reused slot must not inherit the previous utterance's
        # hidden state or last emitted label
        self.state = self._insert(self.state, self._fresh_slot,
                                  jnp.int32(i))

  def run(self, chunk_frames: int = 16) -> list:
    """Drain the submitted queue; returns `SpeechResult`s in retire
    order. Each iteration admits into free slots, feeds every live slot
    its next `chunk_frames` raw frames (finalizing streams that hit end
    of utterance), masked-steps all pending post-frontend frames, and
    retires finished slots so the queue refills them — no slot idles
    while work remains, and no program re-traces across refills."""
    if self._mode == "lockstep":
      raise RuntimeError("server is mid-lockstep-utterance; reset() first")
    results = []
    with tracing.span("speech.run"):
      while self._queue or any(s is not None for s in self._slots):
        self._admit()
        with tracing.span("speech.frontend"):
          chunks, finals = {}, []
          for i, slot in enumerate(self._slots):
            if slot is None or slot.flushed:
              continue
            end = min(slot.fed + chunk_frames, slot.feats.shape[0])
            chunks[i] = slot.feats[None, slot.fed:end]
            slot.fed = end
            if end == slot.feats.shape[0]:
              finals.append(i)
          self._feed(chunks, finals)
        self._decode_pending()
        for i, slot in enumerate(self._slots):
          if slot is not None and slot.done:
            results.append(SpeechResult(
                uid=slot.uid, labels=slot.labels,
                frames=int(slot.feats.shape[0]),
                log_probs=np.stack(slot.log_probs)))
            self._slots[i] = None
    self._mode = None
    return results

  @property
  def occupancy(self) -> float:
    """Live (slot, frame) pairs per decode step, over batch capacity."""
    total = self.decode_steps * self.batch
    return self.busy_steps / total if total else 0.0

  def compile_stats(self) -> dict:
    """Jit cache sizes. The fleet
    contract mirrors LMEngine's: `frame_step` == 1 ever — admits,
    retires, refills, mask patterns and mixed lengths never re-trace —
    and each conv stage holds one signature per pow2 window bucket."""
    return {
        "frame_step": _jit_cache_size(self._frame_step),
        "insert": _jit_cache_size(self._insert),
        "conv1": _jit_cache_size(self._conv1),
        "conv2": _jit_cache_size(self._conv2),
        "conv1_buckets": sorted(self._buckets1),
        "conv2_buckets": sorted(self._buckets2),
    }

  # -- lockstep API (legacy duplex surface) ---------------------------------

  def reset(self) -> None:
    self.state = deepspeech.init_decode_state(self.cfg, self.batch)
    self._slots = [None] * self.batch
    self._queue.clear()
    self._mode = None
    self._finished = False

  def _lockstep_slots(self) -> None:
    if self._mode == "fleet":
      raise RuntimeError("server is mid-fleet-run; reset() first")
    self._mode = "lockstep"
    if all(s is None for s in self._slots):
      for i in range(self.batch):
        s1, s2 = self._make_streams()
        self._slots[i] = _SpeechSlot(None, None, s1, s2)

  def process_chunk(self, feats: np.ndarray, *,
                    final: bool = False) -> list:
    """feats (b, t, feat_dim) raw mel chunk -> newly emitted labels.

    Emission lags the chunk boundary by the frontend's receptive field —
    the context carried so chunked output equals the full forward for
    any total length. Pass final=True (or call flush()) after the last
    chunk; a redundant final/flush is a no-op, new frames after it
    require reset()."""
    feats = np.asarray(feats)
    with tracing.span("speech.process_chunk"):
      if self._finished:
        if feats.shape[1]:
          raise RuntimeError("utterance already finalized; reset() first")
        return [[] for _ in range(self.batch)]
      self._lockstep_slots()
      with tracing.span("speech.frontend"):
        self._feed({i: feats[i:i + 1] for i in range(self.batch)}
                   if feats.shape[1] else {},
                   range(self.batch) if final else ())
      if final:
        self._finished = True
      return self._decode_pending()

  def flush(self) -> list:
    """Drain the right-edge conv context at end of utterance. The right
    pad is computed from the frames actually received, so arbitrary
    (non-stride-multiple) utterance lengths flush cleanly."""
    return self.process_chunk(
        np.zeros((self.batch, 0, self.cfg.feat_dim), np.float32),
        final=True)
