"""Serving driver: --arch selects any decodable config; drives a queue of
mixed-length requests through the continuous-batching LMEngine (or streams
speech through the DS2 server). Smoke configs run on CPU; --full serves
the published config (deepspeech2-wsj at full width fits one TPU v5e).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
      --batch 4 --num-requests 12 --steps 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.data.speech import SpeechDataConfig, batch_at
from repro.models.api import get_model
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import LMEngine, StreamingSpeechServer


def main() -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", required=True, choices=configs.ARCH_NAMES)
  ap.add_argument("--batch", type=int, default=4,
                  help="engine slots (concurrent decode streams)")
  ap.add_argument("--num-requests", type=int, default=None,
                  help="requests to queue (default: --batch); extras "
                       "refill slots as earlier requests retire")
  ap.add_argument("--steps", type=int, default=16,
                  help="per-request new-token budget (requests draw "
                       "varying budgets up to this)")
  ap.add_argument("--prompt-len", type=int, default=8,
                  help="mean prompt length; requests draw varying "
                       "lengths around this")
  ap.add_argument("--max-len", type=int, default=128)
  ap.add_argument("--temperature", type=float, default=0.8)
  ap.add_argument("--eos-id", type=int, default=None,
                  help="token id retiring a request early")
  ap.add_argument("--full", action="store_true")
  ap.add_argument("--kernels", choices=["jnp", "pallas"], default="jnp",
                  help="execution policy: 'pallas' routes the decode "
                       "regime through the shape-specialized kernels "
                       "(kernels.dispatch), 'jnp' is the reference path")
  ap.add_argument("--quantize", action="store_true",
                  help="one-shot PTQ (repro.quant) before serving: every "
                       "GEMM leaf becomes int8 + per-column scales and "
                       "decodes through the int8_gemm regime")
  ap.add_argument("--speculate", type=int, default=0, metavar="K",
                  help="self-speculative decoding: a low-rank draft of "
                       "the SAME params proposes K tokens per step, the "
                       "target verifies them in one batched window "
                       "forward. Greedy (--temperature 0) is lossless — "
                       "token-for-token vanilla greedy; temperature > 0 "
                       "rejection-samples, matching the vanilla "
                       "sampling distribution exactly")
  ap.add_argument("--draft-rank", type=int, default=None,
                  help="fixed truncated-SVD rank for the draft's GEMMs "
                       "(default: explained-variance rule at 0.9)")
  ap.add_argument("--adapt-rank", action="store_true",
                  help="online draft-rank controller: walk --draft-rank "
                       "to keep the measured accept rate inside "
                       "--rank-band (requires --draft-rank)")
  ap.add_argument("--rank-band", type=float, nargs=2, default=(0.5, 0.85),
                  metavar=("LO", "HI"),
                  help="target accept-rate band for --adapt-rank")
  ap.add_argument("--rank-step", type=int, default=16,
                  help="rank increment per --adapt-rank adjustment")
  ap.add_argument("--rank-interval", type=int, default=8,
                  help="engine iterations per --adapt-rank measurement "
                       "window")
  ap.add_argument("--prefix-cache", action="store_true",
                  help="radix-trie prefix cache: shared prompt prefixes "
                       "splice from cached decode-state snapshots and "
                       "only the uncached suffix is prefilled (greedy "
                       "output stays bit-identical to cold serving)")
  ap.add_argument("--prefix-cache-mb", type=float, default=256.0,
                  help="byte-accounted LRU capacity for --prefix-cache")
  args = ap.parse_args()
  enable_compile_cache()
  if args.adapt_rank and args.draft_rank is None:
    ap.error("--adapt-rank needs --draft-rank (a starting rank to walk)")
  if args.adapt_rank and args.quantize:
    ap.error("--adapt-rank rebuilds the draft from the served params, "
             "which int8 leaves cannot be SVD'd from — drop one flag")

  cfg = (configs.get_config(args.arch) if args.full
         else configs.get_smoke(args.arch))
  api = get_model(cfg)
  params = api.init(jax.random.PRNGKey(0), cfg)
  if args.speculate and cfg.family == "deepspeech":
    # the streaming CTC server is frame-synchronous: there is no token
    # sequence to draft, so speculation does not apply — say so instead
    # of silently ignoring the flag
    print("--speculate applies to the LM engine only; the deepspeech "
          "family streams frame-synchronously — ignoring")
    args.speculate = 0
  draft_params = None
  if args.speculate and args.quantize:
    # int8 leaves can't be SVD'd — build the draft from the float
    # weights BEFORE PTQ (quantization x speculation still composes
    # losslessly: verification is against whatever the target computes)
    from repro.serving import make_draft_params
    draft_params = make_draft_params(params, rank=args.draft_rank)
  if args.quantize:
    from repro.core.factored import iter_gemm_leaves
    from repro.quant import QuantizedLinear, quantize_params
    params = quantize_params(params)
    n_int8 = sum(l.num_params for l in iter_gemm_leaves(params)
                 if isinstance(l, QuantizedLinear))
    print(f"PTQ'd {n_int8} GEMM params to int8 "
          f"(serving from quantized storage)")

  if cfg.family == "deepspeech":
    # continuous-batching speech fleet: --num-requests utterances of
    # mixed, deliberately non-stride-multiple lengths share --batch
    # decode slots; retiring utterances refill from the queue without
    # re-tracing (server.compile_stats pins frame_step == 1)
    server = StreamingSpeechServer(cfg, params, batch_size=args.batch,
                                   kernel_policy=args.kernels)
    n_utts = args.num_requests or 2 * args.batch
    dc = SpeechDataConfig(vocab_size=cfg.vocab_size, feat_dim=cfg.feat_dim,
                          global_batch=max(args.batch, 1))
    rng = np.random.RandomState(0)
    for i in range(n_utts):
      batch = np.asarray(batch_at(dc, i)["feats"])
      row = batch[i % batch.shape[0]]
      t = int(rng.randint(17, min(64, row.shape[0]) + 1))
      server.submit(row[:t])                # arbitrary lengths by design
    t0 = time.perf_counter()
    results = server.run(chunk_frames=16)
    dt = time.perf_counter() - t0
    frames = sum(r.frames for r in results)
    stats = server.compile_stats()
    print(f"fleet served {len(results)} utterances ({frames} frames) "
          f"through {args.batch} slots in {dt:.2f}s "
          f"({len(results) / dt:.1f} streams/s, {frames / dt:.0f} "
          f"frames/s, occupancy {server.occupancy:.2f}, "
          f"frame_step signatures {stats['frame_step']})")
    for r in results[:4]:
      print(f"  utt {r.uid}: {r.frames} frames -> "
            f"{len(r.labels)} labels; sample {r.labels[:6]}")
    return

  num_requests = args.num_requests or args.batch
  rng = np.random.RandomState(0)
  lo, hi = max(1, args.prompt_len // 2), 2 * args.prompt_len
  temperature = args.temperature
  cache = None
  if args.prefix_cache:
    from repro.serving import PrefixCache
    cache = PrefixCache(capacity_mb=args.prefix_cache_mb)
  controller = None
  if args.adapt_rank:
    from repro.serving import RankController
    controller = RankController(band=tuple(args.rank_band),
                                step=args.rank_step,
                                interval=args.rank_interval)
  engine = LMEngine(cfg, params, batch_size=args.batch,
                    max_len=args.max_len, kernel_policy=args.kernels,
                    eos_id=args.eos_id, speculate=args.speculate,
                    draft_params=draft_params, draft_rank=args.draft_rank,
                    rank_controller=controller, prefix_cache=cache)
  if args.speculate:
    from repro.core.factored import count_params
    print(f"speculating {args.speculate} tokens/step with a "
          f"{count_params(engine.draft_params)}-param low-rank draft "
          f"(target {count_params(params)})")
  # with a prefix cache, model fleet traffic: most requests open with a
  # shared system-prompt template, so the cache has prefixes to hit
  shared = rng.randint(1, cfg.vocab_size, size=(max(2, args.prompt_len),))
  for _ in range(num_requests):
    prompt = rng.randint(1, cfg.vocab_size, size=(rng.randint(lo, hi + 1),))
    if cache is not None and rng.rand() < 0.8:
      prompt = np.concatenate([shared, prompt])
    engine.submit(prompt, max_new_tokens=int(rng.randint(1, args.steps + 1)))
  t0 = time.perf_counter()
  finished = engine.run(temperature=temperature)
  dt = time.perf_counter() - t0
  tokens = sum(len(f.tokens) for f in finished)
  spec = ""
  if args.speculate:
    # accept_rate is None until something was drafted — "no data", not 0
    rate = engine.accept_rate
    spec = (f", accept rate {rate:.2f}" if rate is not None
            else ", accept rate n/a")
    if args.adapt_rank:
      spec += (f", draft rank {engine.draft_rank} "
               f"({len(engine.rank_history)} adjustments)")
  ttfts = sorted(f.ttft_s for f in finished if f.ttft_s is not None)
  ttft_p50 = ttfts[len(ttfts) // 2] * 1e3 if ttfts else float("nan")
  cachestr = ""
  if cache is not None:
    cs = engine.cache_stats()
    cachestr = (f", cache hit rate {cs['hit_rate']:.2f} "
                f"({cs['entries']} entries, "
                f"{cs['bytes'] / (1 << 20):.1f} MB)")
  print(f"served {len(finished)} requests ({tokens} tokens) through "
        f"{args.batch} slots in {dt:.2f}s ({tokens / dt:.1f} tok/s, "
        f"TTFT p50 {ttft_p50:.1f} ms, "
        f"occupancy {engine.occupancy:.2f}{spec}{cachestr})")
  for f in finished[:4]:
    print(f"  req {f.uid}: prompt {len(f.prompt)} -> {len(f.tokens)} "
          f"tokens ({f.finish_reason}); sample {f.tokens[:6].tolist()}")


if __name__ == "__main__":
  main()
