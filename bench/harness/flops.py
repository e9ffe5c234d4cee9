"""Operation and byte counts, from shapes, kept with the benchmark.

``model_flops_per_token``: the FLOPs the method requires per trained token
(the model FLOPs of ``mfu``).  No remat recompute and no dW are counted:

* LowRank-IPA, per low-rank matmul (k -> n, rank r): forward x W, x V and
  p B^T; input gradient dy W^T, dy B, q V^T; and dB = dy^T p:
  4kn + 4kr + 6nr.
* LowRank-LR (zeroth order): two forwards, 2 (2kn + 2kr + 2nr).
* Causal attention: the score and value products, 4 dh Hq (S+1)/2 per
  token forward, three times that with the backward.
* The SSD chunk products (chunk Q): C B^T (2 Q N G), the masked chunk
  product (2 Q H P), the chunk states and their output (4 N H P) per token
  forward, three times that with the backward.

``kernel_cost``: one call of a fused low-rank kernel: its FLOPs and its
compulsory bytes (each operand read once, each output written once, at its
dtype), not a model of the kernel's tiling.
"""
from __future__ import annotations


def lowrank_matmuls(cfg, lowrank: dict) -> list:
    """[(k, n as stored, n as work, r, count per token)]: the model's
    low-rank matmuls (the unembedding's padded columns are not work)."""
    out = []
    for path, (k, n, r) in lowrank.items():
        layers = cfg.num_layers if path.startswith("['layers']") else 1
        work = cfg.vocab_size if path == "['unembed']" else n
        out.append((k, n, work, r, layers))
    return out


def model_flops_per_token(cfg, lowrank: dict, seq: int, method: str) -> float:
    fwd_only = method == "lowrank_lr"
    total = 0.0
    for k, _, n, r, count in lowrank_matmuls(cfg, lowrank):
        fwd = 2 * k * n + 2 * k * r + 2 * n * r
        per = 2 * fwd if fwd_only else 4 * k * n + 4 * k * r + 6 * n * r
        total += count * per
    mult = 2 if fwd_only else 3
    if cfg.family == "ssm":
        q = min(cfg.ssd_chunk, seq)
        h = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
        n_, p, g = cfg.ssm_state, cfg.ssm_head_dim, max(1, cfg.ssm_groups)
        fwd = 2 * q * n_ * g + 2 * q * h * p + 4 * n_ * h * p
        total += mult * cfg.num_layers * fwd
    else:
        dh = cfg.head_dim or cfg.d_model // cfg.num_heads
        fwd = 4 * dh * cfg.num_heads * (seq + 1) / 2
        total += mult * cfg.num_layers * fwd
    return float(total)


_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
          "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
          "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def nbytes(dtype: str, shape) -> int:
    n = _BYTES[dtype]
    for d in shape:
        n *= d
    return n


def kernel_cost(operands: list, results: list) -> tuple:
    """(flops, compulsory bytes) of one fused low-rank kernel call, from
    its operands and results as [(dtype, shape)].  Recognised by their
    shapes: the forward x (M,K), w (K,N), v (K,r), b (N,r) -> y [, p];
    the backward dy (M,N), w (K,N), v (K,r), b (N,r), p (M,r) -> dx, dB;
    the merge w (K,N), v (K,r), b (N,r) -> w'.  Anything else (the
    elementwise subspace Adam) counts bytes only."""
    byts = sum(nbytes(d, s) for d, s in operands + results)
    shapes = [s for _, s in operands]
    flops = 0.0
    if len(shapes) == 4 and all(len(s) == 2 for s in shapes):
        (m, k), (k2, n), (k3, r), (n2, r2) = shapes
        if k == k2 == k3 and n == n2 and r == r2:
            flops = 2 * m * k * n + 2 * m * k * r + 2 * m * r * n
    elif len(shapes) == 5 and all(len(s) == 2 for s in shapes):
        (m, n), (k, n2), (k2, r), (n3, r2), (m2, r3) = shapes
        if n == n2 == n3 and k == k2 and m == m2 and r == r2 == r3:
            flops = 2 * m * n * k + 4 * m * n * r + 2 * m * r * k
    elif len(shapes) == 3 and all(len(s) == 2 for s in shapes):
        (k, n), (k2, r), (n2, r2) = shapes
        if k == k2 and n == n2 and r == r2:
            flops = 2 * k * n * r
    return float(flops), float(byts)
