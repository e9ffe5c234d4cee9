"""Attention: blockwise (flash-style) GQA, decode-with-cache, and MLA.

:func:`blockwise_attention` takes one of two routes, chosen by
``kernels/dispatch.py`` (``route("attention", ...)``) from what it can
observe of the call:

* ``pallas`` — causal self-attention from position 0 at a head size and a
  sequence of multiples of 128 in bf16, on one TPU device, outside a
  serving prefill (``prefill``; decode never comes here): the Pallas
  flash kernel of ``kernels/flash_attention.py`` (block-skipped causal
  mask, grouped K/V, fp32 softmax state in VMEM, its own backward).
* ``xla`` — every other call: pure-JAX online-softmax blockwise attention.
  Memory is O(S * chunk) instead of O(S^2): queries are processed in
  chunks (``lax.map``), keys/values are streamed in chunks (``lax.scan``),
  and both levels are rematerialised (``jax.checkpoint``) so the backward
  pass never holds full score matrices.

Both compute GQA in grouped form — KV heads are never materialised
repeated — and the same mathematics; only the schedule differs.

Layout conventions:
  q: (B, Sq, Hq, D)   k: (B, Skv, Hkv, D)   v: (B, Skv, Hkv, Dv)
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..kernels import dispatch
from ..kernels.flash_attention import flash_attention, flash_blocks
from ..kernels.ops import _interpret

Array = jax.Array

NEG_INF = -1e30


def _pick_chunk(s: int, target: int) -> int:
    """Largest divisor of ``s`` that is <= target (falls back to s)."""
    if s <= target:
        return s
    for c in range(min(target, s), 0, -1):
        if s % c == 0:
            return c
    return s


def _chunk(x: Array, axis: int, size: int) -> Array:
    """Split ``axis`` into (n_chunks, size)."""
    shape = list(x.shape)
    n = shape[axis] // size
    shape[axis:axis + 1] = [n, size]
    return x.reshape(shape)


@jax.named_scope("repro.attention")
def blockwise_attention(
    q: Array, k: Array, v: Array, *,
    causal: bool = True,
    q_offset=0,
    kv_valid_len: Optional[Array] = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    softmax_scale: Optional[float] = None,
    cp_groups: int = 1,
    prefill: bool = False,
) -> Array:
    """Online-softmax attention, O(S * chunk) memory.

    ``q_offset``: absolute position of q[0] (prefill continuation / decode).
    ``kv_valid_len``: if given, keys at positions >= kv_valid_len are masked
    (decode with a pre-allocated cache).
    ``cp_groups``: context parallelism — split the query sequence into
    contiguous groups folded into the batch dim (each group carries its own
    position offset; KV stays whole).  Used when heads don't divide the TP
    axis: the group dim is shardable over ``model`` (see lm.attn_apply).
    ``prefill``: the call fills a serving KV cache; it keeps the XLA route,
    whatever its shape (the kernel route is training attention).

    q may arrive in a wider dtype than k (the model's fp32 rotary
    output): it is rounded to k's dtype once — on the kernel route after
    the softmax scale is folded in — and the output carries k's dtype.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    fits = (causal and not prefill and isinstance(q_offset, int)
            and q_offset == 0 and kv_valid_len is None and Sq == Skv
            and v.shape[-1] == D and cp_groups == 1
            and flash_blocks(Sq, D, k.dtype) is not None)
    rt = dispatch.route("attention", shapes=(B, Sq, Hq, Hkv, D),
                        dtypes=(q.dtype, k.dtype, v.dtype), fits=fits)
    with dispatch._scope("attention", rt):
        if rt == "pallas":
            return flash_attention(q, k, v, softmax_scale=scale,
                                   interpret=_interpret())
        return _blockwise(q.astype(k.dtype), k, v, causal=causal,
                          q_offset=q_offset, kv_valid_len=kv_valid_len,
                          q_chunk=q_chunk, kv_chunk=kv_chunk, scale=scale,
                          cp_groups=cp_groups)


def _blockwise(q: Array, k: Array, v: Array, *, causal: bool, q_offset,
               kv_valid_len: Optional[Array], q_chunk: int, kv_chunk: int,
               scale: float, cp_groups: int) -> Array:
    """The XLA route of :func:`blockwise_attention`."""
    if cp_groups > 1 and q.shape[1] % cp_groups == 0 and q.shape[1] > 1:
        B, Sq, Hq, D = q.shape
        g = cp_groups
        from ..sharding.ctx import constrain as _c
        qg = _c(q.reshape(B, g, Sq // g, Hq, D), "batch", "tp", None, None,
                None)
        offs = q_offset + (Sq // g) * jnp.arange(g, dtype=jnp.int32)
        out = jax.vmap(
            lambda qq, off: _blockwise(
                qq, k, v, causal=causal, q_offset=off,
                kv_valid_len=kv_valid_len, q_chunk=q_chunk,
                kv_chunk=kv_chunk, scale=scale, cp_groups=1),
            in_axes=(1, 0), out_axes=1)(qg, offs)
        return out.reshape(B, Sq, Hq, out.shape[-1])
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv

    # Pad awkward lengths (vlm prefix, whisper 1500) up to a chunk multiple
    # instead of degrading to tiny divisor chunks; padded keys are masked,
    # padded queries sliced off.
    Sq0, Skv0 = Sq, Skv
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Skv)
    if Sq % qc:
        pad = qc - Sq % qc
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Sq += pad
    if Skv % kc:
        pad = kc - Skv % kc
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_valid_len is None:
            kv_valid_len = jnp.asarray(Skv0, jnp.int32)
        Skv += pad
    nq, nk = Sq // qc, Skv // kc

    # (nq, B, qc, Hkv, G, D) / (nk, B, kc, Hkv, D)
    qr = _chunk(q, 1, qc).reshape(B, nq, qc, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    kr = _chunk(k, 1, kc).transpose(1, 0, 2, 3, 4)
    vr = _chunk(v, 1, kc).transpose(1, 0, 2, 3, 4)

    q_offset = jnp.asarray(q_offset, jnp.int32)

    def one_q_chunk(qi, qblk):
        qpos = q_offset + qi * qc + jnp.arange(qc, dtype=jnp.int32)  # (qc,)

        def kv_step(carry, inp):
            acc, m, l = carry
            ki, kblk, vblk = inp
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            kpos = ki * kc + jnp.arange(kc, dtype=jnp.int32)
            mask = jnp.ones((qc, kc), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if kv_valid_len is not None:
                mask &= (kpos < kv_valid_len)[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        init = (jnp.zeros((B, qc, Hkv, G, Dv), jnp.float32),
                jnp.full((B, qc, Hkv, G), NEG_INF, jnp.float32),
                jnp.zeros((B, qc, Hkv, G), jnp.float32))
        (acc, m, l), _ = jax.lax.scan(
            jax.checkpoint(kv_step, prevent_cse=False),
            init, (jnp.arange(nk, dtype=jnp.int32), kr, vr))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)

    out = jax.lax.map(jax.checkpoint(
        lambda args: one_q_chunk(*args), prevent_cse=False),
        (jnp.arange(nq, dtype=jnp.int32), qr))
    # (nq, B, qc, Hkv, G, Dv) -> (B, Sq, Hq, Dv)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hq, Dv)
    return out[:, :Sq0] if Sq != Sq0 else out


def decode_attention(
    q: Array, k_cache: Array, v_cache: Array, cur_len: Array, *,
    softmax_scale: Optional[float] = None,
) -> Array:
    """Single-token attention over a pre-allocated KV cache.

    q: (B, 1, Hq, D); caches: (B, Smax, Hkv, D/Dv); cur_len: () int32 —
    number of valid cache entries (the new token's K/V must already be
    written at position cur_len - 1).
    """
    B, _, Hq, D = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(Smax, dtype=jnp.int32)
    s = jnp.where((pos < cur_len)[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode cache (serving): fixed-size pages from a shared arena
# ---------------------------------------------------------------------------
#
# Layout: one arena per cache tensor, shaped (n_pages, page, H, D).  A
# sequence owns an ordered list of page ids recorded in its page-table row
# (-1 = unmapped); token t of a sequence lives at arena[table[t // page],
# t % page].  All layers share ONE page-id space: page p holds the same
# token range in every layer's arena, so a single (batch, max_pages) table
# serves the whole model.


def paged_write(arena: Array, new: Array, page_table: Array,
                lengths: Array) -> Array:
    """Scatter one new token per batch slot into a paged arena.

    arena: (n_pages, page, H, D); new: (B, 1, H, D) or (B, H, D);
    page_table: (B, max_pages) int32, -1 = unmapped; lengths: (B,) int32 —
    tokens already stored per slot (the new token lands at position
    ``lengths[b]``).  Slots whose target page is unmapped (inactive rows)
    scatter out of bounds and are dropped.
    """
    if new.ndim == 4:
        new = new[:, 0]
    page = arena.shape[1]
    pidx = jnp.minimum(lengths // page, page_table.shape[1] - 1)
    rows = jnp.take_along_axis(page_table, pidx[:, None], axis=1)[:, 0]
    rows = jnp.where(rows >= 0, rows, arena.shape[0])   # OOB -> dropped
    return arena.at[rows, lengths % page].set(
        new.astype(arena.dtype), mode="drop")


def paged_decode_attention(
    q: Array, k_arena: Array, v_arena: Array, page_table: Array,
    lengths: Array, *, softmax_scale: Optional[float] = None,
) -> Array:
    """Single-token attention over a paged KV arena (online softmax).

    q: (B, 1, Hq, D); arenas: (n_pages, page, Hkv, D / Dv); lengths: (B,)
    int32 — valid tokens per slot INCLUDING the one written this step.
    Pages are visited in slot order, so per-row accumulation order is
    identical to a solo run of the same sequence (bit-stable join/evict).
    Rows with no mapped pages produce finite zeros.
    """
    B, _, Hq, D = q.shape
    n_pages, page, Hkv, _ = k_arena.shape
    Dv = v_arena.shape[-1]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    qg = q.reshape(B, Hkv, G, D)

    def body(carry, j):
        acc, m, l = carry
        rows = page_table[:, j]                              # (B,)
        safe = jnp.maximum(rows, 0)
        kblk = jnp.take(k_arena, safe, axis=0)               # (B,page,Hkv,D)
        vblk = jnp.take(v_arena, safe, axis=0)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kblk,
                       preferred_element_type=jnp.float32) * scale
        pos = j * page + jnp.arange(page, dtype=jnp.int32)
        mask = (rows[:, None] >= 0) & (pos[None, :] < lengths[:, None])
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask[:, None, None, :],
                      jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgk,bkhd->bhgd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    init = (jnp.zeros((B, Hkv, G, Dv), jnp.float32),
            jnp.full((B, Hkv, G), NEG_INF, jnp.float32),
            jnp.zeros((B, Hkv, G), jnp.float32))
    (acc, m, l), _ = jax.lax.scan(
        body, init, jnp.arange(page_table.shape[1], dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, Hq, Dv).astype(q.dtype)


def paged_mla_attention(
    q_eff: Array, q_rope: Array, cc_arena: Array, cr_arena: Array,
    page_table: Array, lengths: Array, *, softmax_scale: float,
) -> Array:
    """Absorbed-MLA decode over paged compressed caches.

    q_eff: (B, H, kvl) fp32 (already absorbed through W_uk); q_rope:
    (B, H, rope); arenas: (n_pages, page, kvl / rope).  Returns the fp32
    context (B, H, kvl) — the caller applies W_uv.
    """
    B, H, kvl = q_eff.shape
    page = cc_arena.shape[1]

    def body(carry, j):
        acc, m, l = carry
        rows = page_table[:, j]
        safe = jnp.maximum(rows, 0)
        cc = jnp.take(cc_arena, safe, axis=0).astype(jnp.float32)
        cr = jnp.take(cr_arena, safe, axis=0).astype(jnp.float32)
        s = (jnp.einsum("bhk,btk->bht", q_eff, cc) +
             jnp.einsum("bhr,btr->bht", q_rope.astype(jnp.float32), cr)
             ) * softmax_scale
        pos = j * page + jnp.arange(page, dtype=jnp.int32)
        mask = (rows[:, None] >= 0) & (pos[None, :] < lengths[:, None])
        s = jnp.where(mask[:, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask[:, None, :], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bht,btk->bhk", p, cc)
        return (acc_new, m_new, l_new), None

    init = (jnp.zeros((B, H, kvl), jnp.float32),
            jnp.full((B, H), NEG_INF, jnp.float32),
            jnp.zeros((B, H), jnp.float32))
    (acc, m, l), _ = jax.lax.scan(
        body, init, jnp.arange(page_table.shape[1], dtype=jnp.int32))
    return acc / jnp.maximum(l, 1e-30)[..., None]


class KVCache(NamedTuple):
    """Per-layer-stacked KV cache. k/v: (L, B, Smax, Hkv, D)."""
    k: Array
    v: Array
    length: Array  # () int32 — valid entries

    @staticmethod
    def alloc(layers: int, batch: int, max_len: int, kv_heads: int,
              head_dim: int, v_dim: Optional[int] = None,
              dtype=jnp.bfloat16) -> "KVCache":
        vd = v_dim or head_dim
        return KVCache(
            k=jnp.zeros((layers, batch, max_len, kv_heads, head_dim), dtype),
            v=jnp.zeros((layers, batch, max_len, kv_heads, vd), dtype),
            length=jnp.zeros((), jnp.int32))

    @staticmethod
    def abstract(layers: int, batch: int, max_len: int, kv_heads: int,
                 head_dim: int, v_dim: Optional[int] = None,
                 dtype=jnp.bfloat16) -> "KVCache":
        vd = v_dim or head_dim
        return KVCache(
            k=jax.ShapeDtypeStruct((layers, batch, max_len, kv_heads,
                                    head_dim), dtype),
            v=jax.ShapeDtypeStruct((layers, batch, max_len, kv_heads, vd),
                                   dtype),
            length=jax.ShapeDtypeStruct((), jnp.int32))


def cache_update(cache_k: Array, cache_v: Array, k_new: Array, v_new: Array,
                 index: Array):
    """Write (B, S_new, Hkv, D) at position ``index`` of (B, Smax, Hkv, D)."""
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k_new.astype(cache_k.dtype), (0, index, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v_new.astype(cache_v.dtype), (0, index, 0, 0))
    return cache_k, cache_v
