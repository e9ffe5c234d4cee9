"""Weights and projections made by the benchmark from the run's seed.

Both the program and the reference take their weights from here, so the
reference never reads anything the program made.  The layout is the
program's parameter tree (``lm.abstract_params``: names and shapes only);
every value is drawn here, leaf by leaf from its own key, in one jitted
call on the device, in the stored dtype.

Low-rank leaves follow the paper's rule: every weight matrix of the
model except the embedding table and the depthwise conv, with
``min(k, n) >= min_dim``.  Each gets a Haar-Stiefel projection
V = sqrt(k / r) Q (Q the thin-QR factor of a Gaussian, column signs fixed
by diag R), the law of Algorithm 2, drawn here and handed to the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .data import seed_key

EXCLUDED = ("['embed']['tok']", "['conv_w']")


def layout(cfg) -> list:
    """[(path, shape, dtype)] in the program's flat-leaf order."""
    from repro.models import lm

    flat = jax.tree_util.tree_flatten_with_path(lm.abstract_params(cfg))[0]
    return [(jax.tree_util.keystr(p), tuple(x.shape), x.dtype)
            for p, x in flat]


def is_layered(path: str) -> bool:
    return path.startswith("['layers']")


def matrix_shape(path: str, shape) -> tuple:
    """(k, n) of a weight matrix leaf, or () for anything else."""
    mat = shape[1:] if is_layered(path) else shape
    return tuple(mat) if len(mat) == 2 else ()


def lowrank_leaves(cfg, rank: int, min_dim: int) -> dict:
    """{path: (k, n, r)} of the leaves trained through B."""
    out = {}
    for path, shape, _ in layout(cfg):
        km = matrix_shape(path, shape)
        if not km or any(path.endswith(e) for e in EXCLUDED):
            continue
        if min(km) >= min_dim:
            out[path] = (km[0], km[1], max(1, min(rank, min(km) // 2)))
    return out


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("[", 1)[-1].strip("[]'")
    f32 = jnp.float32
    if name in ("final_norm", "ln1", "ln2", "norm", "q_norm", "k_norm",
                "d_skip"):
        return jnp.ones(shape, dtype)
    if name in ("conv_b", "bq", "bk", "bv"):
        return jnp.zeros(shape, dtype)
    if name == "a_log":      # A = -exp(a_log), a_log = log U[1, 16]
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":    # softplus(dt_bias) = dt ~ logU[1e-3, 0.1]
        dt = jnp.exp(jax.random.uniform(key, shape, f32, np.log(1e-3),
                                        np.log(0.1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "tok":
        return (0.02 * jax.random.normal(key, shape, f32)).astype(dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return (jax.random.normal(key, shape, f32) / np.sqrt(fan_in)
            ).astype(dtype)


def _stiefel(key, k: int, r: int):
    g = jax.random.normal(key, (k, r), jnp.float32)
    q, rm = jnp.linalg.qr(g, mode="reduced")
    d = jnp.sign(jnp.diagonal(rm))
    return q * jnp.where(d == 0, 1.0, d)[None, :] * np.sqrt(k / r)


def make(cfg, seed: int, lowrank: dict, v_dtype):
    """({path: weight}, {path: V}) for ``cfg`` from ``seed``, on the
    device, in one jitted call."""
    leaves = layout(cfg)
    key = seed_key(seed)

    @jax.jit
    def build(key):
        wkey, vkey = jax.random.split(key)
        ws = {}
        for i, (path, shape, dtype) in enumerate(leaves):
            ws[path] = _leaf(jax.random.fold_in(wkey, i), path, shape, dtype)
        vs = {}
        for i, (path, shape, _) in enumerate(leaves):
            if path not in lowrank:
                continue
            k, _, r = lowrank[path]
            lk = jax.random.fold_in(vkey, i)
            if is_layered(path):
                keys = jax.random.split(lk, shape[0])
                v = jax.vmap(lambda kk: _stiefel(kk, k, r))(keys)
            else:
                v = _stiefel(lk, k, r)
            vs[path] = v.astype(v_dtype)
        return ws, vs

    return build(key)


def tree(cfg, flat: dict):
    """The program's parameter tree from a flat {path: array}."""
    from repro.models import lm

    treedef = jax.tree.structure(lm.abstract_params(cfg))
    return jax.tree.unflatten(treedef, [flat[p] for p, _, _ in layout(cfg)])
