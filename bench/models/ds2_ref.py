"""Plain float32 reference of the DS2 acoustic model, its loss and update.

Written from the paper's description (arXiv:1710.09026 Appendix B: two
strided 2-D convolutions, growing forward-only GRUs, FC, CTC output) in
straightforward `jax.numpy` and `lax` primitives at `HIGHEST` matmul
precision, with no kernels, caches or streaming. It imports nothing of
the program. Its weights are the benchmark's own (`ds2_weights.make`).

Semantics that the paper leaves open follow the system's documented
convention, so that both sides compute the same function:
  * time padding of each conv: a fixed left pad of (k - s) // 2 frames
    and a right pad that completes ceil(t / s) output frames;
  * frequency padding: "SAME", centred;
  * GRU cell (paper eq. 10): z, r = sigmoid(W x + U h + b),
    h~ = tanh(W_h x + b_h + r * (U_h h)), h' = (1 - z) h + z h~;
  * log-probs are a log-softmax over the output layer; CTC blank is 0 and
    the loss is the batch mean of -log p(labels | audio).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def _f32(x):
  return jnp.asarray(x, jnp.float32)


def int8_round(x):
  """x rounded to symmetric per-tensor int8 and back: the control's
  precision, one step below the configuration's bf16."""
  scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
  return jnp.round(x / scale) * scale


def _ste(x):
  """int8_round in the forward pass, identity for the gradient."""
  return x + jax.lax.stop_gradient(int8_round(x) - x)


@jax.custom_vjp
def int8_dot(x, w):
  """x @ w with every operand of the product and of its two gradient
  products rounded to int8: a GEMM trained in int8."""
  return jnp.matmul(int8_round(x), int8_round(w), precision=HI)


def _int8_dot_fwd(x, w):
  return int8_dot(x, w), (x, w)


def _int8_dot_bwd(res, g):
  x, w = res
  gq = int8_round(g)
  dx = jnp.matmul(gq, int8_round(w).T, precision=HI)
  x2, g2 = x.reshape(-1, x.shape[-1]), gq.reshape(-1, g.shape[-1])
  dw = jnp.matmul(int8_round(x2).T, g2, precision=HI)
  return dx, dw


int8_dot.defvjp(_int8_dot_fwd, _int8_dot_bwd)


def _dot(x, w, quant: bool):
  return int8_dot(x, w) if quant else jnp.matmul(x, w, precision=HI)


def matmul(x, g: dict, quant: bool = False):
  """x @ W for a GEMM given as {"w"} or {"u", "v"} (float32, HIGHEST;
  in int8, forward and backward, under `quant`)."""
  if "w" in g:
    return _dot(x, _f32(g["w"]), quant)
  return _dot(_dot(x, _f32(g["u"]), quant), _f32(g["v"]), quant)


def _conv(x, w, t_stride: int, f_stride: int, quant: bool = False):
  k_t, k_f = w.shape[:2]
  t, f = x.shape[1], x.shape[2]
  t_out = -(-t // t_stride)
  pad_l = (k_t - t_stride) // 2
  pad_r = max((t_out - 1) * t_stride + k_t - t - pad_l, 0)
  f_out = -(-f // f_stride)
  f_tot = max((f_out - 1) * f_stride + k_f - f, 0)
  y = jax.lax.conv_general_dilated(
      _ste(x) if quant else x, _ste(_f32(w)) if quant else _f32(w),
      window_strides=(t_stride, f_stride),
      padding=((pad_l, pad_r), (f_tot // 2, f_tot - f_tot // 2)),
      dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
  return jax.nn.relu(y)


def frontend(w: dict, feats, config: dict, lengths=None,
             quant: bool = False):
  """(b, t, feat_dim) -> (b, t', gru_in).

  `lengths` (b,) marks each row's real frames when rows of different
  lengths share a zero-padded batch: the first conv's outputs past a
  row's own ceil(len / stride) frames are zeroed, as that row's own right
  pad would have them, so each row's outputs up to its own frame count
  equal those of the row computed alone."""
  x = _f32(feats)[..., None]
  x = _conv(x, w["conv1"], config["conv1_time_stride"], config["freq_stride"],
            quant)
  if lengths is not None:
    t1 = -(-jnp.asarray(lengths) // config["conv1_time_stride"])
    keep = jnp.arange(x.shape[1])[None, :] < t1[:, None]
    x = jnp.where(keep[:, :, None, None], x, 0.0)
  x = _conv(x, w["conv2"], config["time_stride"], config["freq_stride"],
            quant)
  b, t, f, c = x.shape
  return x.reshape(b, t, f * c)


def gru(w: dict, i: int, x, quant: bool = False):
  """Forward-only GRU layer i over x (b, t, in) -> (b, t, hidden)."""
  bias = _f32(w[f"gru{i}/bias"])
  xw = matmul(x, w[f"gru{i}/nonrec"], quant) + bias
  rec = w[f"gru{i}/rec"]
  hidden = xw.shape[-1] // 3

  def step(h, xw_t):
    hu = matmul(h, rec, quant)
    z = jax.nn.sigmoid(xw_t[:, :hidden] + hu[:, :hidden])
    r = jax.nn.sigmoid(xw_t[:, hidden:2 * hidden] + hu[:, hidden:2 * hidden])
    cand = jnp.tanh(xw_t[:, 2 * hidden:] + r * hu[:, 2 * hidden:])
    h = (1.0 - z) * h + z * cand
    return h, h

  h0 = jnp.zeros((x.shape[0], hidden), jnp.float32)
  _, hs = jax.lax.scan(step, h0, jnp.swapaxes(xw, 0, 1))
  return jnp.swapaxes(hs, 0, 1)


def forward(w: dict, feats, config: dict, lengths=None, quant: bool = False):
  """(b, t, feat_dim) raw mel -> (b, t', vocab) CTC log-probs (see
  `frontend` for `lengths`; `quant` computes every product in int8, the
  control's precision)."""
  with jax.default_matmul_precision("highest"):
    x = frontend(w, feats, config, lengths, quant)
    for i in range(len(config["gru_dims"])):
      x = gru(w, i, x, quant)
    x = jax.nn.relu(matmul(x, w["fc"], quant))
    return jax.nn.log_softmax(matmul(x, w["out"], quant), axis=-1)


def output_lengths(feat_lengths, config: dict):
  t = -(-feat_lengths // config["conv1_time_stride"])
  return -(-t // config["time_stride"])


def ctc_nll(log_probs, lengths, labels, label_lengths):
  """Per-utterance -log p(labels | log_probs) by the CTC forward recursion.

  log_probs (b, t, v); lengths (b,) valid frames; labels (b, l) padded;
  label_lengths (b,). Blank is 0.
  """
  b, t, _ = log_probs.shape
  s = 2 * labels.shape[1] + 1
  ext = jnp.zeros((b, s), jnp.int32).at[:, 1::2].set(labels)
  pos = jnp.arange(s)[None, :]
  valid = pos < 2 * label_lengths[:, None] + 1
  two_back = jnp.pad(ext, ((0, 0), (2, 0)), constant_values=-1)[:, :s]
  skip_ok = (ext != 0) & (ext != two_back)

  def emit(lp_t):                                   # (b, v) -> (b, s)
    return jnp.take_along_axis(lp_t, ext, axis=1)

  init = jnp.where(pos < 2, emit(log_probs[:, 0]), NEG)
  init = jnp.where(valid, init, NEG)

  def step(alpha, inp):
    lp_t, t_i = inp
    a1 = jnp.pad(alpha, ((0, 0), (1, 0)), constant_values=NEG)[:, :s]
    a2 = jnp.pad(alpha, ((0, 0), (2, 0)), constant_values=NEG)[:, :s]
    a2 = jnp.where(skip_ok, a2, NEG)
    new = jnp.logaddexp(jnp.logaddexp(alpha, a1), a2) + emit(lp_t)
    new = jnp.where(valid, new, NEG)
    return jnp.where((t_i < lengths)[:, None], new, alpha), None

  alpha, _ = jax.lax.scan(
      step, init, (jnp.swapaxes(log_probs, 0, 1)[1:], jnp.arange(1, t)))
  end = 2 * label_lengths
  last = jnp.take_along_axis(alpha, end[:, None], axis=1)[:, 0]
  prev = jnp.take_along_axis(alpha, jnp.maximum(end - 1, 0)[:, None],
                             axis=1)[:, 0]
  prev = jnp.where(label_lengths > 0, prev, NEG)
  return -jnp.logaddexp(last, prev)


def trace_norm_penalty(w: dict, lambda_rec: float, lambda_nonrec: float):
  """Sum over factored GEMMs of lambda * (|U|_F^2 + |V|_F^2) / 2 (eq. 3)."""
  total = jnp.zeros((), jnp.float32)
  for name, g in w.items():
    if isinstance(g, dict) and "u" in g:
      lam = lambda_rec if name.endswith("/rec") else lambda_nonrec
      total = total + lam * 0.5 * (jnp.sum(_f32(g["u"]) ** 2) +
                                   jnp.sum(_f32(g["v"]) ** 2))
  return total


def loss(w: dict, batch: dict, config: dict, lambda_rec: float,
         lambda_nonrec: float, quant: bool = False):
  """CTC batch mean plus the trace-norm penalty: the stage-1 objective."""
  lp = forward(w, batch["feats"], config, quant=quant)
  nll = ctc_nll(lp, output_lengths(batch["feat_lengths"], config),
                batch["labels"], batch["label_lengths"])
  return jnp.mean(nll) + trace_norm_penalty(w, lambda_rec, lambda_nonrec)


def leaves(w: dict) -> dict:
  """{leaf name: array}: "conv1", "gru0/rec.u", "gru0/bias", ..."""
  out = {}
  for name, g in w.items():
    if isinstance(g, dict):
      for k, a in g.items():
        out[f"{name}.{k}"] = a
    else:
      out[name] = g
  return out


def adamw_steps(w0: dict, batches: list, config: dict, opt: dict,
                reg: dict, quant: bool = False) -> dict:
  """The stage-1 update (AdamW with global-norm clipping, trace-norm
  penalty) applied once per batch from w0, in float32.

  Returns losses per step, per-leaf norms of the first step's gradient
  as the optimizer takes it (after clipping), and per-leaf norms of the
  parameters' change after the last step."""
  lr, b1, b2, eps = opt["lr"], opt["b1"], opt["b2"], opt["eps"]
  clip, wd = opt["max_grad_norm"], opt["weight_decay"]
  p = {k: _f32(v) for k, v in leaves(w0).items()}
  start = dict(p)

  def objective(flat, batch):
    w = {}
    for k, v in flat.items():
      name, _, part = k.partition(".")
      if part:
        w.setdefault(name, {})[part] = v
      else:
        w[name] = v
    return loss(w, batch, config, reg["lambda_rec"], reg["lambda_nonrec"],
                quant)

  grad_fn = jax.jit(jax.value_and_grad(objective))
  m = {k: jnp.zeros_like(v) for k, v in p.items()}
  v2 = {k: jnp.zeros_like(v) for k, v in p.items()}
  losses, first = [], None
  for step, batch in enumerate(batches, start=1):
    value, g = grad_fn(p, batch)
    losses.append(float(value))
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    if clip > 0:
      g = {k: x * jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
           for k, x in g.items()}
    if first is None:
      first = {k: float(jnp.linalg.norm(x.ravel())) for k, x in g.items()}
    for k in p:
      m[k] = b1 * m[k] + (1 - b1) * g[k]
      v2[k] = b2 * v2[k] + (1 - b2) * g[k] * g[k]
      delta = (m[k] / (1 - b1 ** step)) / (
          jnp.sqrt(v2[k] / (1 - b2 ** step)) + eps)
      if wd and p[k].ndim >= 2:
        delta = delta + wd * p[k]
      p[k] = p[k] - lr * delta
  change = {k: float(jnp.linalg.norm((p[k] - start[k]).ravel())) for k in p}
  return {"losses": losses, "grad_norms": first, "change_norms": change}
