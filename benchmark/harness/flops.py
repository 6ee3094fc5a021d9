"""Model FLOPs of one QT-Opt Bellman step, from a configuration file's
sizes. The benchmark's copy of `utils.profiling.analytic_flops`
("qtopt_step"): a later PR may change the program's count, not the
yardstick's. benchmark/tests pins the two equal as of PR 23.

Counted: the CEM target (torso once per state, then per iteration the
population through the linearity-split head), the critic's forward and
backward (backward = 2 x forward) and the elementwise optimizer tail.
Only valid multiply-adds of SAME convs count (border positions see
fewer taps), as XLA's cost analysis counts them.
"""

from __future__ import annotations


def _same_conv_taps(h: int, k: int, s: int):
  pad_total = max(k - (s if h % s == 0 else h % s), 0)
  pad_low = pad_total // 2
  out = -(-h // s)
  taps = sum(min(i * s - pad_low + k, h) - max(i * s - pad_low, 0)
             for i in range(out))
  return out, taps


def _conv(n, h_in, k, s, ci, co):
  out, taps = _same_conv_taps(h_in, k, s)
  return out, 2 * n * taps * taps * ci * co


def _convs(n, h_in, ci, filters, first_stride):
  total = 0.0
  for i, co in enumerate(filters):
    h_in, f = _conv(n, h_in, 3, first_stride if i == 0 else 2, ci, co)
    total += f + 3 * n * h_in * h_in * co  # batch-norm affine + relu
    ci = co
  return total, h_in, ci


def n_params(model: dict) -> int:
  from benchmark.harness import weights
  total = 0
  for shape in weights.param_shapes(model).values():
    count = 1
    for dim in shape:
      count *= dim
    total += count
  return total


def qtopt_step_flops(config: dict, batch: int) -> float:
  model, cem = config["model"], config["cem"]
  s2d = model["space_to_depth"]
  h = model["image_size"] // max(s2d, 1)
  cin = 3 * max(s2d, 1) ** 2
  torso, head_f = model["torso_filters"], model["head_filters"]
  encode_1, he, ce = _convs(1, h, cin, torso, 1 if s2d > 1 else 2)
  emb = model["action_embedding_size"]
  merge_c = torso[-1] if torso else 3
  embed_row = 2 * (model["action_dim"] * emb + emb * merge_c)
  dims = [head_f[-1] if head_f else merge_c]
  dims += list(model["dense_sizes"]) + [1]
  qhead_row = 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
  rows = batch * cem["population"]
  per_iter = rows * (embed_row + qhead_row)
  if head_f:
    h2, conv0_row = _conv(1, he, 3, 2, ce, head_f[0])
    c1 = head_f[0]
    per_iter += rows * 2 * ce * h2 * h2 * c1   # action GEMM
    per_iter += rows * 2 * h2 * h2 * c1        # merge add + relu
    tail, ht, ct = _convs(rows, h2, c1, head_f[1:], 2)
    per_iter += tail + rows * ht * ht * ct     # + mean pool
    base = batch * encode_1 + batch * conv0_row + ce * conv0_row
  else:
    per_iter += rows * he * he * ce
    base = batch * encode_1
  cem_flops = base + cem["iterations"] * per_iter
  head_fwd, hh, hc = (_convs(1, he, ce, head_f, 2) if head_f
                      else (0.0, he, ce))
  critic_fwd = batch * (encode_1 + head_fwd + hh * hh * hc
                        + embed_row + qhead_row)
  return cem_flops + 3 * critic_fwd + 14 * n_params(model)
