"""The plain f32 DS2 reference against the program, at smoke widths on
the CPU: its forward against `deepspeech.forward` (f32, no kernels), its
log-probs against a fleet run's, its loss against the program's loss."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import ds2
from bench.models import ds2_ref, ds2_weights
from bench.tests.conftest import smoke_cell


def smoke(form="dense"):
  config = dict(smoke_cell("ds2-wsj.live16").config, dtype="float32")
  from repro import configs
  cfg = configs.get_smoke("deepspeech2-wsj").with_(dtype=jnp.float32)
  flat = ds2_weights.make(config, 2 ** 35 + 1, form)
  return config, cfg, flat, ds2_weights.to_program(flat, config)


@pytest.mark.parametrize("form", ["dense", "lowrank", "factored_full"])
def test_forward_matches_program_forward(form):
  from repro.models import deepspeech
  config, cfg, flat, params = smoke(form)
  feats = np.random.default_rng(0).standard_normal((2, 77, 80)).astype(
      np.float32)
  with jax.default_matmul_precision("highest"):
    want = deepspeech.forward(params, jnp.asarray(feats), cfg)
  got = ds2_ref.forward(flat, feats, config)
  assert got.shape == want.shape == (2, 20, 32)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_reference_matches_fleet_log_probs():
  from repro.kernels import dispatch
  from repro.serving import StreamingSpeechServer
  config, cfg, flat, params = smoke()
  rng = np.random.default_rng(1)
  utts = [rng.standard_normal((t, 80)).astype(np.float32)
          for t in (45, 64, 97, 130)]
  with jax.default_matmul_precision("highest"):
    server = StreamingSpeechServer(cfg, params, batch_size=2,
                                   kernel_policy=dispatch.JNP_ONLY)
    uids = [server.submit(u) for u in utts]
    results = {r.uid: r for r in server.run(chunk_frames=16)}
  items = [(u, results[i].log_probs) for i, u in zip(uids, utts)]
  checks = {c.name: c for c in ds2.compare_streams(
      config, flat, items, {"served_gap_nats": 1e-6,
                            "logprob_max_err_nats": 1e-4},
      min_frames=1, block=3, bucket=64)}
  assert all(c.ok for c in checks.values()), checks
  assert checks["frames_compared"].value == 12 + 16 + 25 + 33
  for u, lp in items:
    ref = ds2_ref.forward(flat, u[None], config)[0]
    np.testing.assert_allclose(lp, np.asarray(ref), atol=5e-5)


def test_length_masking_equals_rows_alone():
  config, _, flat, _ = smoke()
  rng = np.random.default_rng(2)
  rows = [rng.standard_normal((t, 80)).astype(np.float32) for t in (97, 40)]
  padded = np.zeros((2, 128, 80), np.float32)
  for i, r in enumerate(rows):
    padded[i, :r.shape[0]] = r
  both = ds2_ref.forward(flat, padded, config, lengths=np.array([97, 40]))
  for i, r in enumerate(rows):
    alone = ds2_ref.forward(flat, r[None], config)[0]
    n = alone.shape[0]
    np.testing.assert_allclose(np.asarray(both[i, :n]), np.asarray(alone),
                               atol=2e-6)


def test_loss_and_update_match_program_training():
  from bench.drivers import train as train_driver
  from repro.core.tracenorm import RegularizerConfig
  from repro.optim import AdamWConfig
  from repro.training import TrainConfig, make_train_step
  config, cfg, flat, params = smoke("factored_full")
  cell = smoke_cell("ds2-wsj.train32", frames=120, label_max=24, batch=3,
                    pool=3, length_s={"mean": 0.91, "min": 0.5, "max": 1.2,
                                      "count": 3})
  run = train_driver.Run(cell, 5, 1.0, False)
  batches = run._pool()[0]
  opt = dict(cell.traffic["optimizer"], lr=1e-2)
  reg = cell.traffic["regularizer"]
  ref = ds2_ref.adamw_steps(flat, batches, config, opt, reg)
  tcfg = TrainConfig(lr=opt["lr"], adam=AdamWConfig(
      max_grad_norm=opt["max_grad_norm"]), regularizer=RegularizerConfig(
          kind="trace", lambda_rec=reg["lambda_rec"],
          lambda_nonrec=reg["lambda_nonrec"]))
  init, step = make_train_step(cfg, tcfg, donate=False)
  state, p, losses = init(params), params, []
  with jax.default_matmul_precision("highest"):
    for i, b in enumerate(batches):
      p, state, m = step(p, state, b, jnp.asarray(i))
      losses.append(float(m["loss"]))
  np.testing.assert_allclose(losses, ref["losses"], rtol=1e-4)
  flat_p, _ = jax.tree_util.tree_flatten_with_path(p)
  flat_0 = jax.tree.leaves(params)
  change = {train_driver.leaf_name(k): float(jnp.linalg.norm(
      (a - b).ravel())) for (k, a), b in zip(flat_p, flat_0)}
  assert set(change) == set(ref["change_norms"])
  for k, v in ref["change_norms"].items():
    assert change[k] == pytest.approx(v, rel=1e-3, abs=1e-6), k
