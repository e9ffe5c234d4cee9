"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

One parameterised implementation; the config decides which blocks are
instantiated.  Layers are stacked on a leading ``L`` axis and driven by
``lax.scan`` (compact HLO — essential for the 88-layer dry-runs) with
``jax.checkpoint`` remat around each block.

Every matmul weight is consumed through :func:`repro.models.linear.linear`,
so the paper's low-rank estimator threads through all families unchanged.

Entry points:
  param_specs / init_params / abstract_params
  forward_hidden(params, tokens, cfg, ...)    -> (B, S, d) final hidden
  prefill(params, tokens, cfg, cache, ...)    -> (hidden_last, cache)
  decode_step(params, token, cfg, cache, ...) -> (logits, cache)
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .attention import (KVCache, blockwise_attention, cache_update,
                        decode_attention, paged_decode_attention,
                        paged_mla_attention, paged_write)
from .common import (ParamSpec, apply_rope, rms_norm, swiglu, tree_abstract,
                     tree_init, act_dtype, prm_dtype)
from .linear import (BatchLRPack, LRPack, grad_dtype_barrier, linear,
                     weight_of)
from .moe import moe_ffn
from .ssm import SSMState, mamba2_mixer
from ..sharding.ctx import constrain, divisible

Array = jax.Array


def _ckpt(fn):
    """Remat for scan bodies: prevent_cse=False avoids the optimization
    barriers that block dtype folding of saved residuals (scan already
    provides the CSE protection remat's barriers exist for)."""
    return jax.checkpoint(fn, prevent_cse=False)

VOCAB_PAD = 256


def padded_vocab(cfg) -> int:
    return -(-cfg.vocab_size // VOCAB_PAD) * VOCAB_PAD


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def _w(shape, axes, init="scaled", dtype=None, cfg=None):
    return ParamSpec(shape, dtype or prm_dtype(cfg), axes, init=init)


def _stack(spec: ParamSpec, n: int) -> ParamSpec:
    return ParamSpec((n,) + spec.shape, spec.dtype,
                     ("layers",) + spec.logical_axes, spec.init, spec.scale)


def _attn_specs(cfg, d):
    dh = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    dt = prm_dtype(cfg)
    s = {
        "wq": _w((d, hq * dh), ("embed", "q_heads"), cfg=cfg),
        "wk": _w((d, hkv * dh), ("embed", "kv_heads"), cfg=cfg),
        "wv": _w((d, hkv * dh), ("embed", "kv_heads"), cfg=cfg),
        "wo": _w((hq * dh, d), ("q_heads", "embed"), cfg=cfg),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((hq * dh,), dt, ("q_heads",), "zeros")
        s["bk"] = ParamSpec((hkv * dh,), dt, ("kv_heads",), "zeros")
        s["bv"] = ParamSpec((hkv * dh,), dt, ("kv_heads",), "zeros")
    if getattr(cfg, "qk_norm", False):
        s["q_norm"] = ParamSpec((dh,), dt, (None,), "ones")
        s["k_norm"] = ParamSpec((dh,), dt, (None,), "ones")
    return s


def _mla_specs(cfg, d):
    dt = prm_dtype(cfg)
    h = cfg.num_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    vd = cfg.v_head_dim
    s = {
        "w_dq": _w((d, cfg.q_lora_rank), ("embed", "q_lora"), cfg=cfg),
        "q_norm": ParamSpec((cfg.q_lora_rank,), dt, (None,), "ones"),
        "w_uq": _w((cfg.q_lora_rank, h * (nope + rope)),
                   ("q_lora", "q_heads"), cfg=cfg),
        "w_dkv": _w((d, cfg.kv_lora_rank + rope), ("embed", "kv_lora"),
                    cfg=cfg),
        "kv_norm": ParamSpec((cfg.kv_lora_rank,), dt, (None,), "ones"),
        "w_uk": _w((cfg.kv_lora_rank, h * nope), ("kv_lora", "q_heads"),
                   cfg=cfg),
        "w_uv": _w((cfg.kv_lora_rank, h * vd), ("kv_lora", "q_heads"),
                   cfg=cfg),
        "wo": _w((h * vd, d), ("q_heads", "embed"), cfg=cfg),
    }
    return s


def _mlp_specs(cfg, d, ff):
    return {
        "w_gate": _w((d, ff), ("embed", "ffn"), cfg=cfg),
        "w_up": _w((d, ff), ("embed", "ffn"), cfg=cfg),
        "w_down": _w((ff, d), ("ffn", "embed"), cfg=cfg),
    }


def _moe_specs(cfg, d):
    e, f = cfg.num_experts, cfg.moe_d_ff
    s = {
        "router": ParamSpec((d, e), jnp.float32, ("embed", "expert"),
                            "scaled"),
        "w_gate": _w((e, d, f), ("expert", "embed", "moe_ffn"), cfg=cfg),
        "w_up": _w((e, d, f), ("expert", "embed", "moe_ffn"), cfg=cfg),
        "w_down": _w((e, f, d), ("expert", "moe_ffn", "embed"), cfg=cfg),
    }
    if cfg.num_shared_experts:
        sw = cfg.num_shared_experts * cfg.moe_d_ff
        s["shared"] = _mlp_specs(cfg, d, sw)
    return s


def _ssm_specs(cfg, d):
    dt = prm_dtype(cfg)
    d_in = cfg.ssm_d_inner
    g = max(1, getattr(cfg, "ssm_groups", 1))
    n = cfg.ssm_state
    h = cfg.ssm_heads
    conv_ch = d_in + 2 * g * n
    return {
        "in_proj": _w((d, 2 * d_in + 2 * g * n + h), ("embed", "ssm_inner"),
                      cfg=cfg),
        "conv_w": ParamSpec((cfg.ssm_conv_dim, conv_ch), dt,
                            (None, "ssm_inner"), "scaled"),
        "conv_b": ParamSpec((conv_ch,), dt, ("ssm_inner",), "zeros"),
        "a_log": ParamSpec((h,), jnp.float32, (None,), "ssm_a"),
        "d_skip": ParamSpec((h,), jnp.float32, (None,), "ones"),
        "dt_bias": ParamSpec((h,), jnp.float32, (None,), "ssm_dt"),
        "norm": ParamSpec((d_in,), dt, ("ssm_inner",), "ones"),
        "out_proj": _w((d_in, d), ("ssm_inner", "embed"), cfg=cfg),
    }


def _norm_spec(cfg, d):
    return ParamSpec((d,), prm_dtype(cfg), (None,), "ones")


def _layer_specs(cfg):
    """Specs of ONE scanned layer (without the leading L axis)."""
    d = cfg.d_model
    fam = cfg.family
    s = {}
    if fam in ("dense", "vlm", "audio"):
        s["ln1"] = _norm_spec(cfg, d)
        s["attn"] = _attn_specs(cfg, d)
        s["ln2"] = _norm_spec(cfg, d)
        s["mlp"] = _mlp_specs(cfg, d, cfg.d_ff)
    elif fam == "moe":
        s["ln1"] = _norm_spec(cfg, d)
        s["attn"] = _mla_specs(cfg, d) if cfg.use_mla else _attn_specs(cfg, d)
        s["ln2"] = _norm_spec(cfg, d)
        s["moe"] = _moe_specs(cfg, d)
    elif fam in ("ssm", "hybrid"):
        s["ln1"] = _norm_spec(cfg, d)
        s["ssm"] = _ssm_specs(cfg, d)
    else:
        raise ValueError(fam)
    return s


def param_specs(cfg) -> dict:
    d = cfg.d_model
    vp = padded_vocab(cfg)
    specs = {
        "embed": {"tok": ParamSpec((vp, d), prm_dtype(cfg),
                                   ("vocab", "embed"), "normal")},
        "final_norm": _norm_spec(cfg, d),
        # unembed: vocab-sharded over `model`, d replicated — FSDP-sharding
        # d makes the chunked-CE loop re-gather it per chunk (§Perf).
        "unembed": ParamSpec((d, vp), prm_dtype(cfg), (None, "vocab"),
                             "scaled"),
    }
    n_scan = cfg.num_layers - cfg.first_dense_layers
    specs["layers"] = jax.tree.map(
        lambda sp: _stack(sp, n_scan), _layer_specs(cfg),
        is_leaf=lambda x: isinstance(x, ParamSpec))
    if cfg.first_dense_layers:  # deepseek: leading dense-MLP layer(s)
        dense_ff = getattr(cfg, "moe_dense_ff", 0) or cfg.d_ff
        ds = {
            "ln1": _norm_spec(cfg, d),
            "attn": _mla_specs(cfg, d) if cfg.use_mla else _attn_specs(cfg, d),
            "ln2": _norm_spec(cfg, d),
            "mlp": _mlp_specs(cfg, d, dense_ff),
        }
        specs["dense_layers"] = jax.tree.map(
            lambda sp: _stack(sp, cfg.first_dense_layers), ds,
            is_leaf=lambda x: isinstance(x, ParamSpec))
    if cfg.family == "hybrid" and cfg.attn_every:
        # zamba2: ONE shared attention+MLP block reused every `attn_every`
        # layers (weight sharing — the zamba2 signature).
        specs["shared_attn"] = {
            "ln1": _norm_spec(cfg, d),
            "attn": _attn_specs(cfg, d),
            "ln2": _norm_spec(cfg, d),
            "mlp": _mlp_specs(cfg, d, cfg.d_ff),
        }
    return specs


def init_params(cfg, key: Array) -> dict:
    return tree_init(key, param_specs(cfg))


def abstract_params(cfg) -> dict:
    return tree_abstract(param_specs(cfg))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _split_heads(x, n_heads, dh):
    return x.reshape(x.shape[:-1] + (n_heads, dh))


def attn_apply(h, p, cfg, *, pos_offset=0, cache=None, cache_index=None,
               causal=True, decode=False, paged=None):
    """GQA attention. Returns (out, (k, v) or updated-cache-slices).

    ``pos_offset`` is a scalar or a per-row ``(B,)`` vector (serving:
    sequences at different depths share one decode batch).  With
    ``paged=(page_table, lengths)`` and ``decode=True`` the cache is a
    pair of paged arenas ``(n_pages, page, Hkv, dh)`` instead of dense
    ``(B, Smax, Hkv, dh)`` slices.
    """
    B, S, d = h.shape
    dh = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    # heads over `model` when divisible; else context parallelism —
    # handles qwen2 (28 q heads) / whisper (12) on the 16-way TP mesh: the
    # query sequence is split into `model`-many groups folded into batch
    # (blockwise_attention cp_groups), each attending to the whole KV.
    heads_ok = divisible("tp", hq)
    # CP fallback: keep q SEQ-sharded — the cp_groups reshape then maps
    # seq/16 shards onto group/16 shards with zero data movement (the
    # group partition IS the seq partition).
    q_ax = ("batch", None, "tp", None) if heads_ok else \
        ("batch", "sp", None, None)
    kv_ax = ("batch", None, "tp", None) if divisible("tp", hkv) else \
        ("batch", None, None, None)
    q = constrain(_split_heads(linear(h, p["wq"], p.get("bq")), hq, dh),
                  *q_ax)
    k = constrain(_split_heads(linear(h, p["wk"], p.get("bk")), hkv, dh),
                  *kv_ax)
    v = constrain(_split_heads(linear(h, p["wv"], p.get("bv")), hkv, dh),
                  *kv_ax)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    positions = jnp.asarray(pos_offset, jnp.int32)[..., None] + \
        jnp.arange(S, dtype=jnp.int32)
    q_attn = q      # the query blockwise attention takes
    if cfg.rope_theta:
        posb = jnp.broadcast_to(positions, (B, S))
        # left in fp32: blockwise attention rounds it once, after the
        # softmax scale where its kernel route folds the scale into q
        q_attn = apply_rope(q.astype(jnp.float32), posb, cfg.rope_theta)
        q = q_attn.astype(q.dtype)
        k = apply_rope(k, posb, cfg.rope_theta)

    new_kv = None
    if decode:
        if paged is not None:
            pt, lengths = paged
            ck, cv = cache  # arenas (n_pages, page, Hkv, dh)
            ck = paged_write(ck, k, pt, lengths)
            cv = paged_write(cv, v, pt, lengths)
            out = paged_decode_attention(q, ck, cv, pt, lengths + 1)
            new_kv = (ck, cv)
        else:
            ck, cv = cache  # (B, Smax, Hkv, dh)
            ck, cv = cache_update(ck, cv, k, v, cache_index)
            out = decode_attention(q, ck, cv, cache_index + S)
            new_kv = (ck, cv)
    else:
        from ..sharding.ctx import get_mesh
        cp = 1
        if not heads_ok and get_mesh() is not None and \
                "model" in get_mesh().shape and \
                S % get_mesh().shape["model"] == 0:
            cp = get_mesh().shape["model"]
        out = blockwise_attention(
            q_attn, k, v, causal=causal, q_offset=pos_offset,
            q_chunk=cfg.attn_chunk // 2, kv_chunk=cfg.attn_chunk,
            cp_groups=cp, prefill=cache is not None)
        if cache is not None:  # prefill: persist k/v
            ck, cv = cache
            new_kv = cache_update(ck, cv, k, v,
                                  0 if cache_index is None else cache_index)
    out = constrain(linear(out.reshape(B, S, hq * dh), p["wo"]),
                    "batch", "sp", None)
    return out, new_kv


def _uk_absorb(q32, p, h, nope):
    """Absorb q_nope through W_uk lazily: (B,H,nope) fp32 -> (B,H,kvl).

    With a packed ``p`` the low-rank correction is applied in rank-r form
    — ``W_uk + V Bᵀ`` is never materialised, matching the lazy serving
    contract of the decode program.
    """
    w = weight_of(p).astype(jnp.float32).reshape(-1, h, nope)
    y = jnp.einsum("bhn,khn->bhk", q32, w)
    if isinstance(p, (LRPack, BatchLRPack)):
        v32 = p.v.astype(jnp.float32)
        if isinstance(p, BatchLRPack):
            b4 = p.b.astype(jnp.float32).reshape(
                p.b.shape[-3], h, nope, -1)
            t = jnp.einsum("bhn,bhnr->bhr", q32, b4)
        else:
            b3 = p.b.astype(jnp.float32).reshape(h, nope, -1)
            t = jnp.einsum("bhn,hnr->bhr", q32, b3)
        y = y + jnp.einsum("bhr,kr->bhk", t, v32)
    return y


def _uv_absorb(ctx, p, h, vd):
    """Absorb the fp32 context through W_uv lazily: (B,H,kvl) -> (B,H,vd)."""
    w = weight_of(p).astype(jnp.float32).reshape(-1, h, vd)
    y = jnp.einsum("bhk,khv->bhv", ctx, w)
    if isinstance(p, (LRPack, BatchLRPack)):
        t = jnp.einsum("bhk,kr->bhr", ctx, p.v.astype(jnp.float32))
        if isinstance(p, BatchLRPack):
            b4 = p.b.astype(jnp.float32).reshape(p.b.shape[-3], h, vd, -1)
            y = y + jnp.einsum("bhr,bhvr->bhv", t, b4)
        else:
            b3 = p.b.astype(jnp.float32).reshape(h, vd, -1)
            y = y + jnp.einsum("bhr,hvr->bhv", t, b3)
    return y


def mla_apply(h, p, cfg, *, pos_offset=0, cache=None, cache_index=None,
              decode=False, paged=None):
    """Multi-head latent attention (deepseek-v2).

    Train/prefill: expand K/V, blockwise attention.
    Decode: absorbed form over the *compressed* cache
    (c_kv: (B,Smax,kv_lora), k_rope: (B,Smax,rope)); with
    ``paged=(page_table, lengths)`` the cache is a pair of 4-D arenas
    ``(n_pages, page, 1, kvl)`` / ``(n_pages, page, 1, rope)``.
    """
    B, S, d = h.shape
    hq = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvl = cfg.kv_lora_rank
    scale = (nope + rope) ** -0.5
    positions = jnp.asarray(pos_offset, jnp.int32)[..., None] + \
        jnp.arange(S, dtype=jnp.int32)
    posb = jnp.broadcast_to(positions, (B, S))

    cq = rms_norm(linear(h, p["w_dq"]), p["q_norm"], cfg.norm_eps)
    q = constrain(_split_heads(linear(cq, p["w_uq"]), hq, nope + rope),
                  "batch", None, "tp", None)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, posb, cfg.rope_theta)

    dkv = linear(h, p["w_dkv"])                            # (B,S,kvl+rope)
    c_kv = rms_norm(dkv[..., :kvl], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(dkv[..., kvl:][:, :, None, :], posb,
                        cfg.rope_theta)[:, :, 0, :]        # (B,S,rope)

    # generic KVCache stores MLA caches as (B, Smax, 1, dim) — normalise.
    # (paged arenas are 4-D too but keep their head axis for paged_write.)
    squeeze_head = False
    if cache is not None and paged is None and cache[0].ndim == 4:
        cache = (cache[0][:, :, 0, :], cache[1][:, :, 0, :])
        squeeze_head = True

    def _rewrap(cc, cr):
        if squeeze_head:
            return (cc[:, :, None, :], cr[:, :, None, :])
        return (cc, cr)

    if decode:
        # absorbed attention: q_eff[b,h,:] = W_uk[h] @ q_nope[b,h,:]
        # (lazy low-rank correction applied inside _uk_absorb/_uv_absorb)
        q_eff = _uk_absorb(q_nope[:, 0].astype(jnp.float32), p["w_uk"],
                           hq, nope)                       # (B,H,kvl)
        if paged is not None:
            pt, lengths = paged
            cc_a, cr_a = cache          # (n_pages, page, 1, kvl / rope)
            cc_a = paged_write(cc_a, c_kv, pt, lengths)
            cr_a = paged_write(cr_a, k_rope, pt, lengths)
            ctx = paged_mla_attention(
                q_eff, q_rope[:, 0], cc_a[:, :, 0, :], cr_a[:, :, 0, :],
                pt, lengths + 1, softmax_scale=scale)
            new_cache = (cc_a, cr_a)
        else:
            cc, cr = cache                                 # compressed cache
            cc = jax.lax.dynamic_update_slice(
                cc, c_kv.astype(cc.dtype), (0, cache_index, 0))
            cr = jax.lax.dynamic_update_slice(
                cr, k_rope.astype(cr.dtype), (0, cache_index, 0))
            s = (jnp.einsum("bhk,btk->bht", q_eff, cc.astype(jnp.float32)) +
                 jnp.einsum("bhr,btr->bht", q_rope[:, 0].astype(jnp.float32),
                            cr.astype(jnp.float32))) * scale
            valid = jnp.arange(cc.shape[1]) < (cache_index + S)
            s = jnp.where(valid[None, None, :], s, -1e30)
            pattn = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bht,btk->bhk", pattn, cc.astype(jnp.float32))
            new_cache = _rewrap(cc, cr)
        out = _uv_absorb(ctx, p["w_uv"], hq, vd)
        out = out.reshape(B, 1, hq * vd).astype(h.dtype)
    else:
        k_nope = constrain(_split_heads(linear(c_kv, p["w_uk"]), hq, nope),
                           "batch", None, "tp", None)
        v = constrain(_split_heads(linear(c_kv, p["w_uv"]), hq, vd),
                      "batch", None, "tp", None)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, hq, rope))], axis=-1)
        qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = blockwise_attention(
            qfull, k, v, causal=True, q_offset=pos_offset,
            q_chunk=cfg.attn_chunk // 2, kv_chunk=cfg.attn_chunk,
            softmax_scale=scale)
        out = out.reshape(B, S, hq * vd)
        new_cache = None
        if cache is not None:
            cc, cr = cache
            cc = jax.lax.dynamic_update_slice(
                cc, c_kv.astype(cc.dtype), (0, cache_index or 0, 0))
            cr = jax.lax.dynamic_update_slice(
                cr, k_rope.astype(cr.dtype), (0, cache_index or 0, 0))
            new_cache = _rewrap(cc, cr)
    return constrain(linear(out, p["wo"]), "batch", "sp", None), new_cache


def mlp_apply(h, p, cfg):
    inner = constrain(swiglu(linear(h, p["w_gate"]), linear(h, p["w_up"])),
                      "batch", None, "tp")
    return constrain(linear(inner, p["w_down"]), "batch", "sp", None)


def dense_block(h, p, cfg, **kw):
    a, kv = attn_apply(rms_norm(h, p["ln1"], cfg.norm_eps), p["attn"], cfg,
                       **kw)
    h = constrain(h + a, "batch", "sp", None)
    h = h + mlp_apply(rms_norm(h, p["ln2"], cfg.norm_eps), p["mlp"], cfg)
    return grad_dtype_barrier(constrain(h, "batch", "sp", None)), kv, None


def moe_block(h, p, cfg, **kw):
    if cfg.use_mla:
        a, kv = mla_apply(rms_norm(h, p["ln1"], cfg.norm_eps), p["attn"],
                          cfg, **kw)
    else:
        a, kv = attn_apply(rms_norm(h, p["ln1"], cfg.norm_eps), p["attn"],
                           cfg, **kw)
    h = h + a
    hn = rms_norm(h, p["ln2"], cfg.norm_eps)
    moe_out, aux = moe_ffn(
        hn, p["moe"]["router"], p["moe"]["w_gate"], p["moe"]["w_up"],
        p["moe"]["w_down"], top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor,
        norm_topk=getattr(cfg, "norm_topk", True),
        groups=getattr(cfg, "moe_groups", 1))
    if "shared" in p["moe"]:
        moe_out = moe_out + mlp_apply(hn, p["moe"]["shared"], cfg)
    h = h + moe_out
    return h, kv, aux


# ---------------------------------------------------------------------------
# Forward (train / eval): full-sequence, scan over layers
# ---------------------------------------------------------------------------

def _group_layers(tree, attn_every: int, n_groups: int):
    """Split L-stacked layer params into (n_groups, attn_every, ...) main
    and (L - n_groups*attn_every, ...) tail."""
    main = jax.tree.map(
        lambda x: x[:n_groups * attn_every].reshape(
            (n_groups, attn_every) + x.shape[1:]), tree)
    tail = jax.tree.map(lambda x: x[n_groups * attn_every:], tree)
    return main, tail


def _embed(params, tokens, cfg, extra_embeds=None):
    emb = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if extra_embeds is not None:  # vlm / audio stub frontend
        emb = jnp.concatenate([extra_embeds.astype(emb.dtype), emb], axis=1)
    return constrain(emb, "batch", "sp", None)


def forward_hidden(params, tokens, cfg, *, extra_embeds=None):
    """(B, S) tokens -> (B, S_total, d) final hidden (post final-norm)."""
    h = _embed(params, tokens, cfg, extra_embeds)
    aux_acc = jnp.zeros((2,), jnp.float32)  # (lb_loss, router_z) sums
    fam = cfg.family

    if cfg.first_dense_layers:
        def dense0_body(h, lp):
            if cfg.use_mla:
                a, _ = mla_apply(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                 lp["attn"], cfg)
            else:
                a, _ = attn_apply(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                  lp["attn"], cfg)
            h = h + a
            h = h + mlp_apply(rms_norm(h, lp["ln2"], cfg.norm_eps),
                              lp["mlp"], cfg)
            return h, None
        h, _ = jax.lax.scan(_ckpt(dense0_body), h,
                            params["dense_layers"])

    if fam in ("dense", "vlm", "audio"):
        def body(h, lp):
            h, _, _ = dense_block(h, lp, cfg)
            return h, None
        h, _ = jax.lax.scan(_ckpt(body), h, params["layers"])
    elif fam == "moe":
        def body(carry, lp):
            h, aux = carry
            h, _, a = moe_block(h, lp, cfg)
            aux = aux + jnp.stack([a["lb_loss"], a["router_z"]])
            return (h, aux), None
        (h, aux_acc), _ = jax.lax.scan(_ckpt(body), (h, aux_acc),
                                       params["layers"])
    elif fam in ("ssm", "hybrid"):
        shared = params.get("shared_attn")

        def mamba_body(h, lp):
            m, _ = mamba2_mixer(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                lp["ssm"], cfg)
            return h + m, None

        if shared is not None and cfg.attn_every:
            # zamba2: scan over GROUPS of attn_every mamba layers, each
            # followed by the shared attention+MLP block (no lax.cond —
            # static structure keeps HLO flops/collectives exact).
            main, tail = _group_layers(params["layers"], cfg.attn_every,
                                       cfg.num_layers // cfg.attn_every)

            def group_body(h, gp):
                h, _ = jax.lax.scan(_ckpt(mamba_body), h, gp)
                h, _, _ = dense_block(h, shared, cfg)
                return h, None

            h, _ = jax.lax.scan(_ckpt(group_body), h, main)
            if cfg.num_layers % cfg.attn_every:
                h, _ = jax.lax.scan(_ckpt(mamba_body), h, tail)
        else:
            h, _ = jax.lax.scan(_ckpt(mamba_body), h,
                                params["layers"])
    else:
        raise ValueError(fam)

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h, {"lb_loss": aux_acc[0], "router_z": aux_acc[1]}


def logits(params, hidden, cfg):
    """Full logits (small models / decode only — train uses chunked CE)."""
    lg = linear(hidden, params["unembed"])
    vp = padded_vocab(cfg)
    if vp != cfg.vocab_size:
        mask = jnp.arange(vp) < cfg.vocab_size
        lg = jnp.where(mask, lg, -1e30)
    return lg


# ---------------------------------------------------------------------------
# Serving: prefill + decode with caches
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    kv: Optional[KVCache]        # dense/moe/vlm (MLA: k<-c_kv, v<-k_rope)
    ssm: Optional[SSMState]      # ssm/hybrid
    shared_kv: Optional[KVCache]  # hybrid shared-attn apps
    pos: Array                   # () int32 — tokens already in cache


def _n_attn_apps(cfg) -> int:
    return (cfg.num_layers // cfg.attn_every) if cfg.attn_every else 0


def alloc_decode_state(cfg, batch: int, max_len: int,
                       abstract: bool = False) -> DecodeState:
    mk = KVCache.abstract if abstract else KVCache.alloc
    mks = SSMState.abstract if abstract else SSMState.alloc
    dt = act_dtype(cfg)
    kv = ssm = shared = None
    fam = cfg.family
    if fam in ("dense", "vlm", "audio", "moe"):
        if cfg.use_mla:
            kv = mk(cfg.num_layers, batch, max_len, 1, cfg.kv_lora_rank,
                    v_dim=cfg.qk_rope_dim, dtype=dt)
        else:
            kv = mk(cfg.num_layers, batch, max_len, cfg.num_kv_heads,
                    cfg.resolved_head_dim, dtype=dt)
    if fam in ("ssm", "hybrid"):
        g = max(1, getattr(cfg, "ssm_groups", 1))
        conv_ch = cfg.ssm_d_inner + 2 * g * cfg.ssm_state
        ssm = mks(cfg.num_layers, batch, cfg.ssm_heads, cfg.ssm_state,
                  cfg.ssm_head_dim, cfg.ssm_conv_dim, conv_ch, dtype=dt)
        if cfg.attn_every:
            shared = mk(_n_attn_apps(cfg), batch, max_len,
                        cfg.num_kv_heads, cfg.resolved_head_dim, dtype=dt)
    if abstract:
        pos = jax.ShapeDtypeStruct((), jnp.int32)
    else:
        pos = jnp.zeros((), jnp.int32)
    return DecodeState(kv, ssm, shared, pos)


def decode_step(params, token, cfg, state: DecodeState,
                extra_embeds=None):
    """One-token decode. token: (B, 1) int32. Returns (logits, new state)."""
    h = _embed(params, token, cfg, extra_embeds)
    pos = state.pos
    fam = cfg.family
    new_kv = state.kv
    new_ssm = state.ssm
    new_shared = state.shared_kv

    if cfg.first_dense_layers:
        # unscanned leading layers use cache slots [0:first_dense_layers]
        def d0_body(carry, xs):
            h, = carry
            lp, ck, cv = xs
            if cfg.use_mla:
                a, kvs = mla_apply(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   lp["attn"], cfg, pos_offset=pos,
                                   cache=(ck, cv), cache_index=pos,
                                   decode=True)
            else:
                a, kvs = attn_apply(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                    lp["attn"], cfg, pos_offset=pos,
                                    cache=(ck, cv), cache_index=pos,
                                    decode=True)
            h = h + a
            h = h + mlp_apply(rms_norm(h, lp["ln2"], cfg.norm_eps),
                              lp["mlp"], cfg)
            return (h,), kvs
        nfd = cfg.first_dense_layers
        (h,), kvs = jax.lax.scan(
            d0_body, (h,),
            (params["dense_layers"], state.kv.k[:nfd], state.kv.v[:nfd]))
        new_kv = new_kv._replace(
            k=jax.lax.dynamic_update_slice_in_dim(new_kv.k, kvs[0], 0, 0),
            v=jax.lax.dynamic_update_slice_in_dim(new_kv.v, kvs[1], 0, 0))

    if fam in ("dense", "vlm", "audio", "moe"):
        off = cfg.first_dense_layers

        def body(h, xs):
            lp, ck, cv = xs
            if fam == "moe":
                h, kvs, _ = moe_block(h, lp, cfg, pos_offset=pos,
                                      cache=(ck, cv), cache_index=pos,
                                      decode=True)
            else:
                h, kvs, _ = dense_block(h, lp, cfg, pos_offset=pos,
                                        cache=(ck, cv), cache_index=pos,
                                        decode=True)
            return h, kvs
        h, kvs = jax.lax.scan(
            body, h, (params["layers"], state.kv.k[off:], state.kv.v[off:]))
        new_kv = new_kv._replace(
            k=jax.lax.dynamic_update_slice_in_dim(new_kv.k, kvs[0], off, 0),
            v=jax.lax.dynamic_update_slice_in_dim(new_kv.v, kvs[1], off, 0))
    elif fam in ("ssm", "hybrid"):
        shared = params.get("shared_attn")

        def mamba_step(h, xs):
            lp, s_ssm, s_conv = xs
            m, (ns, nc) = mamba2_mixer(
                rms_norm(h, lp["ln1"], cfg.norm_eps), lp["ssm"], cfg,
                ssm_state=s_ssm, conv_state=s_conv, decode=True)
            return h + m, (ns, nc)

        if shared is not None and cfg.attn_every:
            ae = cfg.attn_every
            ng = cfg.num_layers // ae
            main_p, tail_p = _group_layers(params["layers"], ae, ng)

            def regroup(x):
                return (x[:ng * ae].reshape((ng, ae) + x.shape[1:]),
                        x[ng * ae:])

            ssm_m, ssm_t = regroup(state.ssm.ssm)
            conv_m, conv_t = regroup(state.ssm.conv)

            def group_body(h, xs):
                gp, gs, gc, ck, cv = xs
                h, (ns, nc) = jax.lax.scan(mamba_step, h, (gp, gs, gc))
                a, (nk, nv) = attn_apply(
                    rms_norm(h, shared["ln1"], cfg.norm_eps),
                    shared["attn"], cfg, pos_offset=pos, cache=(ck, cv),
                    cache_index=pos, decode=True)
                h = h + a
                h = h + mlp_apply(rms_norm(h, shared["ln2"], cfg.norm_eps),
                                  shared["mlp"], cfg)
                return h, (ns, nc, nk, nv)

            h, (ns_m, nc_m, nk, nv) = jax.lax.scan(
                group_body, h,
                (main_p, ssm_m, conv_m, state.shared_kv.k,
                 state.shared_kv.v))
            ns_all = ns_m.reshape((ng * ae,) + ns_m.shape[2:])
            nc_all = nc_m.reshape((ng * ae,) + nc_m.shape[2:])
            if cfg.num_layers % ae:
                h, (ns_t, nc_t) = jax.lax.scan(
                    mamba_step, h, (tail_p, ssm_t, conv_t))
                ns_all = jnp.concatenate([ns_all, ns_t], axis=0)
                nc_all = jnp.concatenate([nc_all, nc_t], axis=0)
            new_ssm = SSMState(ssm=ns_all, conv=nc_all)
            new_shared = state.shared_kv._replace(k=nk, v=nv,
                                                  length=pos + 1)
        else:
            h, (ns, nc) = jax.lax.scan(
                mamba_step, h,
                (params["layers"], state.ssm.ssm, state.ssm.conv))
            new_ssm = SSMState(ssm=ns, conv=nc)
    else:
        raise ValueError(fam)

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    lg = logits(params, h, cfg)
    if new_kv is not None:
        new_kv = new_kv._replace(length=pos + 1)
    return lg, DecodeState(new_kv, new_ssm, new_shared, pos + 1)


def prefill(params, tokens, cfg, state: DecodeState, extra_embeds=None):
    """Prefill: full forward writing caches; returns (last-pos logits, state).

    Implemented as forward_hidden for hidden states plus cache writes per
    layer; for simplicity and HLO-compactness we recompute K/V per layer in
    a scan identical to training but with cache outputs.
    """
    h = _embed(params, tokens, cfg, extra_embeds)
    B, S = h.shape[0], h.shape[1]
    fam = cfg.family
    new_kv = state.kv
    new_ssm = state.ssm
    new_shared = state.shared_kv

    if cfg.first_dense_layers:
        def d0(h, xs):
            lp, ck, cv = xs
            if cfg.use_mla:
                a, kvs = mla_apply(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   lp["attn"], cfg, cache=(ck, cv),
                                   cache_index=0)
            else:
                a, kvs = attn_apply(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                    lp["attn"], cfg, cache=(ck, cv),
                                    cache_index=0)
            h = h + a
            h = h + mlp_apply(rms_norm(h, lp["ln2"], cfg.norm_eps),
                              lp["mlp"], cfg)
            return h, kvs
        nfd = cfg.first_dense_layers
        h, kvs = jax.lax.scan(
            jax.checkpoint(d0), h,
            (params["dense_layers"], state.kv.k[:nfd], state.kv.v[:nfd]))
        new_kv = new_kv._replace(
            k=jax.lax.dynamic_update_slice_in_dim(new_kv.k, kvs[0], 0, 0),
            v=jax.lax.dynamic_update_slice_in_dim(new_kv.v, kvs[1], 0, 0))

    if fam in ("dense", "vlm", "audio", "moe"):
        off = cfg.first_dense_layers

        def body(h, xs):
            lp, ck, cv = xs
            if fam == "moe":
                h, kvs, _ = moe_block(h, lp, cfg, cache=(ck, cv),
                                      cache_index=0)
            else:
                h, kvs, _ = dense_block(h, lp, cfg, cache=(ck, cv),
                                        cache_index=0)
            return h, kvs
        h, kvs = jax.lax.scan(
            jax.checkpoint(body), h,
            (params["layers"], state.kv.k[off:], state.kv.v[off:]))
        new_kv = new_kv._replace(
            k=jax.lax.dynamic_update_slice_in_dim(new_kv.k, kvs[0], off, 0),
            v=jax.lax.dynamic_update_slice_in_dim(new_kv.v, kvs[1], off, 0),
            length=jnp.asarray(h.shape[1], jnp.int32))
    elif fam in ("ssm", "hybrid"):
        shared = params.get("shared_attn")

        def mamba_body(h, lp):
            hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
            m, (ns, nc) = mamba2_mixer(hn, lp["ssm"], cfg, want_state=True)
            return h + m, (ns, nc)

        if shared is not None and cfg.attn_every:
            ae = cfg.attn_every
            ng = cfg.num_layers // ae
            main_p, tail_p = _group_layers(params["layers"], ae, ng)

            def group_body(h, xs):
                gp, ck, cv = xs
                h, (ns, nc) = jax.lax.scan(
                    jax.checkpoint(mamba_body), h, gp)
                a, (nk, nv) = attn_apply(
                    rms_norm(h, shared["ln1"], cfg.norm_eps),
                    shared["attn"], cfg, cache=(ck, cv), cache_index=0)
                h = h + a
                h = h + mlp_apply(rms_norm(h, shared["ln2"], cfg.norm_eps),
                                  shared["mlp"], cfg)
                return h, (ns, nc, nk, nv)

            h, (ns_m, nc_m, nk, nv) = jax.lax.scan(
                jax.checkpoint(group_body), h,
                (main_p, state.shared_kv.k, state.shared_kv.v))
            ns_all = ns_m.reshape((ng * ae,) + ns_m.shape[2:])
            nc_all = nc_m.reshape((ng * ae,) + nc_m.shape[2:])
            if cfg.num_layers % ae:
                h, (ns_t, nc_t) = jax.lax.scan(
                    jax.checkpoint(mamba_body), h, tail_p)
                ns_all = jnp.concatenate([ns_all, ns_t], axis=0)
                nc_all = jnp.concatenate([nc_all, nc_t], axis=0)
            new_ssm = SSMState(ssm=ns_all,
                               conv=nc_all.astype(state.ssm.conv.dtype))
            new_shared = state.shared_kv._replace(
                k=nk, v=nv, length=jnp.asarray(S, jnp.int32))
        else:
            h, (ns, nc) = jax.lax.scan(_ckpt(mamba_body), h,
                                       params["layers"])
            new_ssm = SSMState(ssm=ns, conv=nc.astype(state.ssm.conv.dtype))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    last = logits(params, h[:, -1:], cfg)
    # pos counts *all* cached positions, including a vlm/audio prefix.
    return last, DecodeState(new_kv, new_ssm, new_shared,
                             jnp.asarray(h.shape[1], jnp.int32))


# ---------------------------------------------------------------------------
# Serving: paged decode state (shared page arena across ragged sequences)
# ---------------------------------------------------------------------------

class PagedDecodeState(NamedTuple):
    """Per-slot paged decode caches (serving engine).

    ``kv_k`` / ``kv_v``: ``(L, n_pages, page, H, D)`` arenas (MLA stores
    the compressed ``c_kv`` / ``k_rope`` with ``H == 1``).  ``ssm``:
    slot-indexed :class:`SSMState` — recurrent state is O(1) per slot, so
    it is not paged.  ``shared_k`` / ``shared_v``: hybrid shared-attention
    arenas ``(n_attn_apps, n_pages, page, Hkv, dh)``.  ``page_table``:
    ``(batch, max_pages)`` int32, ``-1`` = unmapped; ONE page-id space is
    shared by every layer (page p holds the same token range everywhere).
    ``lengths``: ``(batch,)`` int32 tokens stored per slot; ``0`` marks an
    inactive slot (all its arena writes drop, its logits are ignored).
    """
    kv_k: Optional[Array]
    kv_v: Optional[Array]
    ssm: Optional[SSMState]
    shared_k: Optional[Array]
    shared_v: Optional[Array]
    page_table: Array
    lengths: Array


def alloc_paged_state(cfg, batch: int, num_pages: int, page_size: int,
                      max_len: int, abstract: bool = False
                      ) -> PagedDecodeState:
    """Allocate paged decode arenas: ``num_pages`` pages of ``page_size``
    tokens shared by up to ``batch`` concurrent sequences of at most
    ``max_len`` tokens each."""
    dt = act_dtype(cfg)
    fam = cfg.family
    max_pages = -(-max_len // page_size)

    def mk(shape, dtype=dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    kv_k = kv_v = ssm = sk = sv = None
    if fam in ("dense", "vlm", "audio", "moe"):
        if cfg.use_mla:
            kv_k = mk((cfg.num_layers, num_pages, page_size, 1,
                       cfg.kv_lora_rank))
            kv_v = mk((cfg.num_layers, num_pages, page_size, 1,
                       cfg.qk_rope_dim))
        else:
            shp = (cfg.num_layers, num_pages, page_size,
                   cfg.num_kv_heads, cfg.resolved_head_dim)
            kv_k, kv_v = mk(shp), mk(shp)
    if fam in ("ssm", "hybrid"):
        g = max(1, getattr(cfg, "ssm_groups", 1))
        conv_ch = cfg.ssm_d_inner + 2 * g * cfg.ssm_state
        mks = SSMState.abstract if abstract else SSMState.alloc
        ssm = mks(cfg.num_layers, batch, cfg.ssm_heads, cfg.ssm_state,
                  cfg.ssm_head_dim, cfg.ssm_conv_dim, conv_ch, dtype=dt)
        if cfg.attn_every:
            shp = (_n_attn_apps(cfg), num_pages, page_size,
                   cfg.num_kv_heads, cfg.resolved_head_dim)
            sk, sv = mk(shp), mk(shp)
    if abstract:
        pt = jax.ShapeDtypeStruct((batch, max_pages), jnp.int32)
        ln = jax.ShapeDtypeStruct((batch,), jnp.int32)
    else:
        pt = jnp.full((batch, max_pages), -1, jnp.int32)
        ln = jnp.zeros((batch,), jnp.int32)
    return PagedDecodeState(kv_k, kv_v, ssm, sk, sv, pt, ln)


def decode_step_paged(params, token, cfg, state: PagedDecodeState,
                      extra_embeds=None):
    """One-token decode over paged caches. token: (B, 1) int32.

    Slot ``b``'s new token lands at position ``lengths[b]`` of its page
    chain; rows with ``lengths == 0`` are inactive — their cache writes
    scatter out of bounds (dropped) and their logits are finite garbage
    the engine never reads.  Because every per-slot operation (rope
    offsets, page-chain scan order, scatter targets) is row-local, a
    sequence decoded inside a mixed batch is bit-identical to the same
    sequence decoded solo (fp32).
    """
    h = _embed(params, token, cfg, extra_embeds)
    pt, lengths = state.page_table, state.lengths
    fam = cfg.family
    new_kk, new_kv_ = state.kv_k, state.kv_v
    new_ssm = state.ssm
    new_sk, new_sv = state.shared_k, state.shared_v

    if cfg.first_dense_layers:
        # unscanned leading layers use arena slots [0:first_dense_layers]
        def d0_body(h, xs):
            lp, ck, cv = xs
            ap = mla_apply if cfg.use_mla else attn_apply
            a, kvs = ap(rms_norm(h, lp["ln1"], cfg.norm_eps), lp["attn"],
                        cfg, pos_offset=lengths, cache=(ck, cv),
                        decode=True, paged=(pt, lengths))
            h = h + a
            h = h + mlp_apply(rms_norm(h, lp["ln2"], cfg.norm_eps),
                              lp["mlp"], cfg)
            return h, kvs
        nfd = cfg.first_dense_layers
        h, kvs = jax.lax.scan(
            d0_body, h,
            (params["dense_layers"], state.kv_k[:nfd], state.kv_v[:nfd]))
        new_kk = jax.lax.dynamic_update_slice_in_dim(new_kk, kvs[0], 0, 0)
        new_kv_ = jax.lax.dynamic_update_slice_in_dim(new_kv_, kvs[1], 0, 0)

    if fam in ("dense", "vlm", "audio", "moe"):
        off = cfg.first_dense_layers

        def body(h, xs):
            lp, ck, cv = xs
            blk = moe_block if fam == "moe" else dense_block
            h, kvs, _ = blk(h, lp, cfg, pos_offset=lengths,
                            cache=(ck, cv), decode=True,
                            paged=(pt, lengths))
            return h, kvs
        h, kvs = jax.lax.scan(
            body, h, (params["layers"], state.kv_k[off:], state.kv_v[off:]))
        new_kk = jax.lax.dynamic_update_slice_in_dim(new_kk, kvs[0], off, 0)
        new_kv_ = jax.lax.dynamic_update_slice_in_dim(new_kv_, kvs[1], off, 0)
    elif fam in ("ssm", "hybrid"):
        shared = params.get("shared_attn")

        def mamba_step(h, xs):
            lp, s_ssm, s_conv = xs
            m, (ns, nc) = mamba2_mixer(
                rms_norm(h, lp["ln1"], cfg.norm_eps), lp["ssm"], cfg,
                ssm_state=s_ssm, conv_state=s_conv, decode=True)
            return h + m, (ns, nc)

        if shared is not None and cfg.attn_every:
            ae = cfg.attn_every
            ng = cfg.num_layers // ae
            main_p, tail_p = _group_layers(params["layers"], ae, ng)

            def regroup(x):
                return (x[:ng * ae].reshape((ng, ae) + x.shape[1:]),
                        x[ng * ae:])

            ssm_m, ssm_t = regroup(state.ssm.ssm)
            conv_m, conv_t = regroup(state.ssm.conv)

            def group_body(h, xs):
                gp, gs, gc, ck, cv = xs
                h, (ns, nc) = jax.lax.scan(mamba_step, h, (gp, gs, gc))
                a, (nk, nv) = attn_apply(
                    rms_norm(h, shared["ln1"], cfg.norm_eps),
                    shared["attn"], cfg, pos_offset=lengths,
                    cache=(ck, cv), decode=True, paged=(pt, lengths))
                h = h + a
                h = h + mlp_apply(rms_norm(h, shared["ln2"], cfg.norm_eps),
                                  shared["mlp"], cfg)
                return h, (ns, nc, nk, nv)

            h, (ns_m, nc_m, nk, nv) = jax.lax.scan(
                group_body, h,
                (main_p, ssm_m, conv_m, state.shared_k, state.shared_v))
            ns_all = ns_m.reshape((ng * ae,) + ns_m.shape[2:])
            nc_all = nc_m.reshape((ng * ae,) + nc_m.shape[2:])
            if cfg.num_layers % ae:
                h, (ns_t, nc_t) = jax.lax.scan(
                    mamba_step, h, (tail_p, ssm_t, conv_t))
                ns_all = jnp.concatenate([ns_all, ns_t], axis=0)
                nc_all = jnp.concatenate([nc_all, nc_t], axis=0)
            new_ssm = SSMState(ssm=ns_all, conv=nc_all)
            new_sk, new_sv = nk, nv
        else:
            h, (ns, nc) = jax.lax.scan(
                mamba_step, h,
                (params["layers"], state.ssm.ssm, state.ssm.conv))
            new_ssm = SSMState(ssm=ns, conv=nc)
    else:
        raise ValueError(fam)

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    lg = logits(params, h, cfg)
    active = lengths > 0
    new_len = jnp.where(active, lengths + 1, 0)
    return lg, PagedDecodeState(new_kk, new_kv_, new_ssm, new_sk, new_sv,
                                pt, new_len)
