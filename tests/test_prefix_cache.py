"""Prefix-cache tests: trie semantics, byte-accounted LRU eviction, the
snapshot/splice contract per model family, and the serving guarantee —
cached-splice greedy output is token-for-token identical to cold serving
across families x kernel policies x float/PTQ weights, including under
eviction churn.

`match_longest_prefix` also carries a hypothesis property (maximality +
insert/lookup round-trip) against a dict-of-prefixes oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.api import get_model
from repro.serving import LMEngine, PrefixCache
from repro.serving.prefix_cache import _TOKEN_OVERHEAD_BYTES, snapshot_bytes


def _payload(nbytes: int):
  return {"x": np.zeros((nbytes,), np.uint8)}


# ---------------------------------------------------------------------------
# Trie semantics.
# ---------------------------------------------------------------------------


def test_match_longest_prefix_maximality():
  c = PrefixCache(capacity_mb=1)
  c.insert([1, 2], "ab")
  c.insert([1, 2, 3, 4], "abcd")
  c.insert([5], "e")
  # the deepest inserted entry prefixing the query wins
  assert c.match_longest_prefix([1, 2, 3, 4, 9]) == (4, "abcd")
  # a partial edge match cannot host an entry
  assert c.match_longest_prefix([1, 2, 3, 9]) == (2, "ab")
  assert c.match_longest_prefix([1, 9]) == (0, None)
  assert c.match_longest_prefix([5, 5]) == (1, "e")
  assert c.match_longest_prefix([]) == (0, None)
  # pure: no counters moved
  assert c.hits == c.misses == 0


def test_edge_split_on_divergent_insert():
  c = PrefixCache(capacity_mb=1)
  c.insert([1, 2, 3, 4], "deep")
  c.insert([1, 2, 9], "fork")      # splits the (1,2,3,4) edge at depth 2
  assert c.match_longest_prefix([1, 2, 3, 4]) == (4, "deep")
  assert c.match_longest_prefix([1, 2, 9, 7]) == (3, "fork")
  c.insert([1, 2], "mid")          # entry lands exactly on the split node
  assert c.match_longest_prefix([1, 2, 8]) == (2, "mid")


def test_common_prefix_len_sees_partial_edges():
  c = PrefixCache(capacity_mb=1)
  c.insert([1, 2, 3, 4, 5, 6], "a")
  # no entry prefixes the query, but the trie has observed 4 shared
  # tokens — the fork-materialization signal
  assert c.match_longest_prefix([1, 2, 3, 4, 9, 9]) == (0, None)
  assert c.common_prefix_len([1, 2, 3, 4, 9, 9]) == 4
  assert c.common_prefix_len([7, 8]) == 0
  assert c.common_prefix_len([1, 2, 3, 4, 5, 6, 7]) == 6


def test_lookup_counts_and_refreshes_recency():
  c = PrefixCache(capacity_mb=1)
  c.insert([1, 2], _payload(100))
  assert c.lookup([1, 2, 3])[0] == 2
  assert c.lookup([9])[0] == 0
  s = c.stats()
  assert (s["hits"], s["misses"]) == (1, 1)
  assert s["hit_rate"] == 0.5


# ---------------------------------------------------------------------------
# Byte accounting + LRU eviction.
# ---------------------------------------------------------------------------


def test_bytes_accounting_and_lru_eviction():
  kib = 1 << 10
  cap_entries = 3
  # each entry: 1 KiB payload + key overhead for a 2-token key
  per = kib + 2 * _TOKEN_OVERHEAD_BYTES
  c = PrefixCache(capacity_mb=cap_entries * per / (1 << 20))
  for i in range(cap_entries):
    assert c.insert([i, i], _payload(kib))
  assert c.bytes == cap_entries * per
  # touch entry 0 so entry 1 is now LRU
  assert c.lookup([0, 0])[0] == 2
  assert c.insert([7, 7], _payload(kib))
  s = c.stats()
  assert s["evictions"] == 1 and s["entries"] == cap_entries
  assert c.match_longest_prefix([1, 1])[0] == 0      # the LRU went
  assert c.match_longest_prefix([0, 0])[0] == 2      # the touched stayed
  assert c.bytes == cap_entries * per


def test_oversize_rejected_not_admitted():
  c = PrefixCache(capacity_mb=0.001)   # ~1 KiB
  assert not c.insert([1], _payload(1 << 20))
  assert c.stats()["rejected_oversize"] == 1
  assert len(c) == 0 and c.bytes == 0


def test_reinsert_replaces_payload_and_bytes():
  c = PrefixCache(capacity_mb=1)
  c.insert([1, 2], _payload(100))
  b0 = c.bytes
  c.insert([1, 2], _payload(300))
  assert c.match_longest_prefix([1, 2])[1]["x"].size == 300
  assert c.bytes == b0 + 200
  assert len(c) == 1


def test_eviction_prunes_and_remerges_trie():
  c = PrefixCache(capacity_mb=1)
  c.insert([1, 2, 3, 4], "deep")
  c.insert([1, 2, 9], "fork")
  # evict everything via clear-less path: insert huge entries that force
  # LRU eviction of both, then verify lookups are clean and re-insert works
  per = snapshot_bytes(_payload(1 << 19))
  cap = c.capacity_bytes
  n_fit = cap // (per + _TOKEN_OVERHEAD_BYTES)
  for i in range(int(n_fit) + 1):
    c.insert([100 + i], _payload(1 << 19))
  assert c.match_longest_prefix([1, 2, 3, 4])[0] == 0
  assert c.match_longest_prefix([1, 2, 9])[0] == 0
  c.insert([1, 2, 3, 4], "again")
  assert c.match_longest_prefix([1, 2, 3, 4]) == (4, "again")


def test_invalid_args():
  with pytest.raises(ValueError):
    PrefixCache(capacity_mb=0)
  with pytest.raises(ValueError):
    PrefixCache(capacity_mb=1, fork_min_tokens=0)
  c = PrefixCache(capacity_mb=1)
  with pytest.raises(ValueError):
    c.insert([], "empty")
  with pytest.raises(ValueError):
    c.insert(np.zeros((2, 2), np.int32), "2d")


# ---------------------------------------------------------------------------
# hypothesis property: trie == dict-of-prefixes oracle.
# ---------------------------------------------------------------------------


def test_match_longest_prefix_property():
  hyp = pytest.importorskip("hypothesis")
  st = pytest.importorskip("hypothesis.strategies")

  keys = st.lists(st.lists(st.integers(0, 3), min_size=1, max_size=6)
                  .map(tuple), min_size=0, max_size=12, unique=True)
  query = st.lists(st.integers(0, 3), min_size=0, max_size=8)

  @hyp.given(keys=keys, q=query)
  @hyp.settings(max_examples=200, deadline=None)
  def prop(keys, q):
    c = PrefixCache(capacity_mb=64)
    oracle = {}
    for k in keys:
      c.insert(list(k), ("payload", k))
      oracle[k] = ("payload", k)
    # round-trip: every inserted key matches itself exactly
    for k in keys:
      assert c.match_longest_prefix(list(k)) == (len(k), oracle[k])
    # maximality vs the oracle
    best = max((k for k in oracle if tuple(q[:len(k)]) == k),
               key=len, default=None)
    m, payload = c.match_longest_prefix(q)
    if best is None:
      assert (m, payload) == (0, None)
    else:
      assert m == len(best) and payload == oracle[best]

  prop()


# ---------------------------------------------------------------------------
# Snapshot/splice contract per family.
# ---------------------------------------------------------------------------

FAMILIES_FAST = ["qwen3-4b", "zamba2-7b"]
FAMILIES_SLOW = ["xlstm-350m", "deepseek-v2-lite"]


def _roundtrip(arch):
  """Decode t tokens, snapshot the prefix, splice into a fresh state:
  the spliced state must equal the decoded state bit-for-bit (rows past
  t are zeros in both — init state is zeros and the scatter only wrote
  [0, t))."""
  cfg = configs.get_smoke(arch).with_(vocab_size=64, dtype=jnp.float32)
  api = get_model(cfg)
  params = api.init(jax.random.PRNGKey(0), cfg)
  t, max_len = 5, 16
  state = api.init_decode_state(cfg, 1, max_len)
  toks = np.random.RandomState(0).randint(1, 64, size=(t,))
  for i in range(t):
    _, state = api.decode_step(params, state,
                               jnp.asarray([[toks[i]]], jnp.int32),
                               jnp.asarray([i], jnp.int32), cfg)
  snap = api.prefix_view(cfg, state, t)
  fresh = api.init_decode_state(cfg, 1, max_len)
  spliced = api.splice_prefix(cfg, fresh, snap)
  for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(spliced)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # and snapshot bytes are the accounting unit the cache charges
  assert snapshot_bytes(snap) > 0


@pytest.mark.parametrize("arch", FAMILIES_FAST)
def test_prefix_view_splice_roundtrip(arch):
  _roundtrip(arch)


@pytest.mark.slow
@pytest.mark.parametrize("arch", FAMILIES_SLOW)
def test_prefix_view_splice_roundtrip_slow(arch):
  _roundtrip(arch)


# ---------------------------------------------------------------------------
# Engine parity: cached-splice == cold, token-for-token.
# ---------------------------------------------------------------------------


def _shared_prefix_prompts(vocab=60, n_shared=4, share=6, suffix=4):
  rng = np.random.RandomState(0)
  shared = rng.randint(1, vocab, size=(share,))
  out = [np.concatenate([shared, rng.randint(1, vocab, size=(suffix,))])
         for _ in range(n_shared)]
  out.append(rng.randint(1, vocab, size=(5,)))   # one unrelated request
  return out


def _serve(cfg, params, prompts, cache, *, policy=None, budget=6):
  eng = LMEngine(cfg, params, batch_size=2, max_len=32,
                 kernel_policy=policy, prefix_cache=cache)
  for p in prompts:
    eng.submit(p, max_new_tokens=budget)
  return {f.uid: tuple(f.tokens) for f in eng.run()}, eng


def test_engine_cached_splice_parity_and_hits():
  cfg = configs.get_smoke("qwen3-4b").with_(vocab_size=64)
  api = get_model(cfg)
  params = api.init(jax.random.PRNGKey(0), cfg)
  prompts = _shared_prefix_prompts()

  cold, ceng = _serve(cfg, params, prompts, None)
  warm, weng = _serve(cfg, params, prompts, PrefixCache(capacity_mb=64))
  assert warm == cold
  cs = weng.cache_stats()
  # fork materialization: the 2nd shared request publishes the template,
  # the 3rd onward splice it — hits, not just inserts
  assert cs["hits"] >= 2 and cs["inserts"] >= len(prompts)
  assert 0.0 < cs["hit_rate"] < 1.0
  # compile contract survives the splice path
  stats = weng.compile_stats()
  assert stats["step"] == 1
  assert stats["prefill"] == len(stats["prefill_buckets"])
  # per-bucket invocation counts: every prefill call is attributed
  assert sum(stats["prefill_calls"].values()) >= len(prompts)
  assert set(stats["prefill_calls"]) == {
      f"{b}x{p}" for b, p in stats["prefill_buckets"]}
  # a cache-less engine exposes the same zeroed surface
  z = ceng.cache_stats()
  assert set(z) == set(cs) and z["hits"] == 0 and z["hit_rate"] == 0.0


def test_engine_parity_under_eviction_churn():
  """A capacity that holds ~2 entries forces eviction mid-serve; parity
  must be indifferent to WHAT the cache remembers."""
  cfg = configs.get_smoke("qwen3-4b").with_(vocab_size=64)
  api = get_model(cfg)
  params = api.init(jax.random.PRNGKey(0), cfg)
  prompts = _shared_prefix_prompts()

  probe = PrefixCache(capacity_mb=64)
  _serve(cfg, params, prompts[:1], probe)
  per_entry = probe.bytes          # one published full-prompt snapshot

  tiny = PrefixCache(capacity_mb=2.5 * per_entry / (1 << 20))
  cold, _ = _serve(cfg, params, prompts, None)
  warm, _ = _serve(cfg, params, prompts, tiny)
  assert warm == cold
  assert tiny.stats()["evictions"] > 0
  assert tiny.bytes <= tiny.capacity_bytes


def test_publish_on_retire_multiturn_hit():
  """Turn 2 = turn-1 prompt + generated tokens + new user tokens: with
  publish_on_retire the whole served conversation is a cached prefix."""
  cfg = configs.get_smoke("qwen3-4b").with_(vocab_size=64)
  api = get_model(cfg)
  params = api.init(jax.random.PRNGKey(0), cfg)
  rng = np.random.RandomState(1)
  cache = PrefixCache(capacity_mb=64)
  eng = LMEngine(cfg, params, batch_size=2, max_len=32,
                 prefix_cache=cache, publish_on_retire=True)
  eng.submit(rng.randint(1, 64, size=(6,)), max_new_tokens=4)
  f1 = eng.run()[0]
  assert f1.ttft_s is not None and f1.ttft_s > 0

  turn2 = np.concatenate([f1.prompt, f1.tokens,
                          rng.randint(1, 64, size=(2,))])
  h0 = cache.hits
  eng.submit(turn2, max_new_tokens=4)
  wf = eng.run()[0]
  assert cache.hits > h0

  ceng = LMEngine(cfg, params, batch_size=2, max_len=32)
  ceng.submit(turn2, max_new_tokens=4)
  np.testing.assert_array_equal(wf.tokens, ceng.run()[0].tokens)


@pytest.mark.slow
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("policy", [None, "pallas"])
@pytest.mark.parametrize("arch", ["qwen3-4b", "zamba2-7b"])
def test_cached_splice_parity_grid(arch, policy, quant):
  """The acceptance grid: cached-splice == cold token-for-token across
  an attention family and an SSM-hybrid family, jnp and Pallas kernel
  policies, float and PTQ'd weights, mixed prefix-share lengths."""
  cfg = configs.get_smoke(arch).with_(vocab_size=64, dtype=jnp.float32)
  api = get_model(cfg)
  params = api.init(jax.random.PRNGKey(0), cfg)
  if quant:
    from repro.quant import quantize_params
    params = quantize_params(params)
  rng = np.random.RandomState(2)
  shared = rng.randint(1, 64, size=(8,))
  # mixed prefix-share lengths, each depth occurring twice past the
  # first sighting: request 2 forks at depth 8 and publishes it, request
  # 3 hits it; request 4 forks at depth 5, request 5 hits that
  prompts = [np.concatenate([shared[:k], rng.randint(1, 64, size=(3,))])
             for k in (8, 8, 8, 5, 5)]
  prompts.append(rng.randint(1, 64, size=(4,)))

  cold, _ = _serve(cfg, params, prompts, None, policy=policy, budget=5)
  cache = PrefixCache(capacity_mb=64)
  warm, _ = _serve(cfg, params, prompts, cache, policy=policy, budget=5)
  assert warm == cold
  assert cache.stats()["hits"] >= 2
