"""Chunked cross-entropy.

Logits for a (B, S, vocab~150k) block at once would dominate activation
memory; we scan over sequence chunks, computing (B, chunk, vocab) logits,
reducing to per-token CE immediately, and remat the chunk so the backward
pass recomputes logits instead of storing them.  The unembedding flows
through :func:`linear`, so the low-rank estimator covers the LM head.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..models.linear import linear
from ..sharding.ctx import constrain

Array = jax.Array


@jax.named_scope("repro.loss")
def chunked_ce(hidden: Array, unembed, labels: Array, *,
               true_vocab: int, chunk: int = 512,
               label_mask: Optional[Array] = None):
    """Mean CE over (B, S) labels; hidden (B, S, d).

    ``unembed`` may be an Array or LRPack; padded-vocab columns are masked
    out of the logsumexp so padding never changes the loss.
    """
    B, S, d = hidden.shape
    c = min(chunk, S)
    while S % c:
        c -= 1
    n = S // c
    h = hidden.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    y = labels.reshape(B, n, c).transpose(1, 0, 2)
    if label_mask is None:
        m = jnp.ones((n, B, c), jnp.float32)
    else:
        m = label_mask.reshape(B, n, c).transpose(1, 0, 2).astype(jnp.float32)

    vp = unembed.shape[-1] if isinstance(unembed, jax.Array) else \
        unembed.w.shape[-1]
    col_ok = (jnp.arange(vp) < true_vocab)

    def one_chunk(args):
        hc, yc, mc = args
        lg = constrain(linear(hc, unembed), "batch", None, "tp"
                       ).astype(jnp.float32)
        lg = jnp.where(col_ok, lg, -1e30)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, yc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - picked) * mc), jnp.sum(mc)

    totals = jax.lax.map(jax.checkpoint(one_chunk), (h, y, m))
    return jnp.sum(totals[0]) / jnp.maximum(jnp.sum(totals[1]), 1.0)


def cls_ce(logits: Array, labels: Array) -> Array:
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def cls_accuracy(logits: Array, labels: Array) -> Array:
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
