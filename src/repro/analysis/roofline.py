"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), per the assignment:

  compute    = HLO_FLOPs          / (chips * PEAK_FLOPS)
  memory     = HLO_bytes_accessed / (chips * HBM_BW)
  collective = collective_bytes   / (chips * ICI_BW)

HLO_FLOPs / bytes come from ``compiled.cost_analysis()``; collective bytes
are parsed from the optimized HLO text (all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operand sizes).

MODEL_FLOPS = 6 N D (dense) or 6 N_active D (MoE) gives the useful-compute
ratio that flags remat/dispatch waste.
"""
from __future__ import annotations

import re
from typing import Dict

# TPU v5e hardware constants (assignment-specified)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (assignment: ~50 GB/s/link)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[16,512,3584]{2,1,0}  or  f32[128]
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum of output-shape bytes of every collective op, by kind.

    Uses the op's result shape (per-shard) — the data each device moves in
    one invocation — matching the per-chip link-bandwidth denominator.
    """
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result-shape = op-name(...) ; skip fusions referencing collectives
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (\(?[^=]*?\)?) "
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)", s)
        if not m:
            continue
        kind = m.group(2)
        shapes = _SHAPE_RE.finditer(m.group(1))
        total = sum(_shape_bytes(x) for x in shapes)
        out[kind] += total
    return out


def model_flops(cfg, shape, kind: str) -> float:
    """6 * N * D (train) / 2 * N * D (inference) with N = *matmul-
    participating* active params (token-embedding gathers do no FLOPs) and
    D = tokens/step."""
    n = matmul_param_count(cfg)
    if cfg.family == "moe":
        n = n - _routed_inactive(cfg)
    if kind == "train":
        tokens = shape.global_batch * (
            cfg.max_decode_len if cfg.is_encoder_decoder else shape.seq_len)
        return 6.0 * n * tokens
    if kind == "prefill":
        if cfg.is_encoder_decoder:
            # prefill = encoder pass (enc params x enc tokens) + 1 dec token
            enc = _subtree_count(cfg, "enc")
            return 2.0 * shape.global_batch * (
                enc * cfg.encoder_seq + (n - enc))
        return 2.0 * n * shape.global_batch * shape.seq_len
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n * tokens


def param_count(cfg) -> int:
    import jax
    from ..models import encdec, lm
    model = encdec if cfg.is_encoder_decoder else lm
    specs = model.param_specs(cfg)
    total = 0
    for s in jax.tree.leaves(
            specs, is_leaf=lambda x: hasattr(x, "shape")):
        n = 1
        for d in s.shape:
            n *= d
        total += n
    return total


def matmul_param_count(cfg) -> int:
    """Params that participate in per-token matmuls (embedding gathers and
    decoder-side caches excluded)."""
    import jax
    from ..models import encdec, lm
    model = encdec if cfg.is_encoder_decoder else lm
    specs = model.param_specs(cfg)
    total = 0
    for path, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: hasattr(x, "shape"))[0]:
        keys = "/".join(str(getattr(p, "key", "")) for p in path)
        if "/tok" in keys or keys.endswith("pos") or "embed/" in keys:
            continue
        n = 1
        for d in s.shape:
            n *= d
        total += n
    return total


def _subtree_count(cfg, sub: str) -> int:
    import jax
    from ..models import encdec
    specs = encdec.param_specs(cfg)[sub]
    return sum(int(np_prod(s.shape)) for s in jax.tree.leaves(
        specs, is_leaf=lambda x: hasattr(x, "shape")))


def np_prod(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def _routed_inactive(cfg) -> int:
    d, f, e, k = cfg.d_model, cfg.moe_d_ff, cfg.num_experts, cfg.top_k
    n_moe_layers = cfg.num_layers - cfg.first_dense_layers
    return n_moe_layers * (e - k) * 3 * d * f


def active_param_count(cfg) -> int:
    """MoE: only top-k routed experts (+ shared) count as active."""
    total = param_count(cfg)
    if cfg.family != "moe":
        return total
    return total - _routed_inactive(cfg)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# Serving: paged decode-cache footprint + lazy-adapter decode traffic
# ---------------------------------------------------------------------------

def cache_token_bytes(cfg, itemsize: int = 2) -> dict:
    """Decode-cache footprint of ONE sequence, split into the part that
    grows with its length (``per_token``) and the part that does not
    (``fixed`` — the SSM recurrent/conv state, fp32 ssm + act-dtype conv
    per the SSMState contract).  Mirrors ``lm.alloc_paged_state``'s
    geometry exactly: MLA caches the compressed (kv_lora + rope) latents
    with a single head, dense/moe/vlm cache (K, V) per kv-head, hybrids
    add one shared-attention KV per ``attn_every`` group."""
    per_tok, fixed = 0, 0
    fam = cfg.family
    if fam in ("dense", "vlm", "audio", "moe"):
        if cfg.use_mla:
            per_tok += cfg.num_layers * (
                cfg.kv_lora_rank + cfg.qk_rope_dim) * itemsize
        else:
            per_tok += cfg.num_layers * 2 * cfg.num_kv_heads * \
                cfg.resolved_head_dim * itemsize
    if fam in ("ssm", "hybrid"):
        g = max(1, getattr(cfg, "ssm_groups", 1))
        conv_ch = cfg.ssm_d_inner + 2 * g * cfg.ssm_state
        fixed += cfg.num_layers * (
            cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim * 4
            + (cfg.ssm_conv_dim - 1) * conv_ch * itemsize)
        if cfg.attn_every:
            n_apps = cfg.num_layers // cfg.attn_every
            per_tok += n_apps * 2 * cfg.num_kv_heads * \
                cfg.resolved_head_dim * itemsize
    return {"per_token": per_tok, "fixed": fixed}


def paged_cache_bytes(cfg, lengths, page_size: int,
                      itemsize: int = 2) -> int:
    """Arena bytes actually HELD by sequences of the given lengths under
    page_size-token paging: each sequence owns ceil(len/page) pages (the
    last one partially filled), plus its fixed slot state."""
    t = cache_token_bytes(cfg, itemsize)
    total = 0
    for n in lengths:
        total += _cdiv(int(n), page_size) * page_size * t["per_token"]
        total += t["fixed"]
    return total


def dense_cache_bytes(cfg, batch: int, max_len: int,
                      itemsize: int = 2) -> int:
    """The pre-paging comparator: every slot reserves ``max_len`` tokens
    up front (``lm.alloc_decode_state``) regardless of actual length."""
    t = cache_token_bytes(cfg, itemsize)
    return batch * (max_len * t["per_token"] + t["fixed"])


def serve_decode_bytes(groups, batch: int, tenants: int,
                       compute_dtype: str = "bf16") -> dict:
    """Weight-stream HBM bytes of ONE multi-tenant batched decode step,
    lazy vs merged.

    ``groups``: iterable of ``(k, n, r, members)``, one entry per low-rank
    group (``members`` = stacked leaves).  Lazy serving streams each base W
    once, the shared V once, and one rank-r B per decode row
    (``k n + k r + batch·n r`` elements per member leaf); merged serving
    of ``tenants`` distinct adapter sets must stream a full (k, n) weight
    per tenant (``tenants·k n``) — the traffic the paged engine's
    ``W + V Bᵀ`` path avoids."""
    sz = _DTYPE_BYTES.get(compute_dtype, 2)
    lazy = merged = 0.0
    for (k, n, r, members) in groups:
        lazy += members * (k * n + k * r + batch * n * r) * sz
        merged += members * max(1, tenants) * k * n * sz
    return {"lazy_bytes": lazy, "merged_bytes": merged,
            "reduction": 1.0 - lazy / merged if merged else 0.0,
            "batch": batch, "tenants": tenants,
            "compute_dtype": compute_dtype}


def roofline_terms(record: dict, cfg=None, shape=None) -> dict:
    """Three roofline terms (seconds) from one dry-run record.

    The memory term uses ``bytes_min`` (dot/gather/collective traffic —
    assumes producer-consumer fusion of elementwise chains, which the TPU
    backend performs but the CPU-backend HLO dump does not); the
    all-ops upper bound is reported as ``t_memory_upper_s``.
    """
    chips = record["devices"]
    flops = record["cost"]["flops"] or 0.0
    bytes_up = record["cost"]["bytes_accessed"] or 0.0
    bytes_min = record["cost"].get("bytes_min", bytes_up) or bytes_up
    coll = sum(record["collectives"].values())
    # cost_analysis flops are per-program (per-device under SPMD)
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_min / HBM_BW
    t_coll = coll / ICI_BW
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    out = {
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_memory_upper_s": bytes_up / HBM_BW,
        "t_collective_s": t_coll, "dominant": dominant,
        "bound_s": max(t_compute, t_memory, t_coll),
    }
    if cfg is not None and shape is not None:
        mf = model_flops(cfg, shape, record["kind"])
        out["model_flops"] = mf
        out["useful_ratio"] = mf / (flops * chips) if flops else 0.0
        # fraction of roofline: useful work per chip over the bound time
        out["roofline_frac"] = (mf / chips / PEAK_FLOPS) / out["bound_s"] \
            if out["bound_s"] else 0.0
    return out
