"""The plain reference: the paper's LowRank-IPA step in float32 jax.numpy.

It imports nothing of the program.  It takes the benchmark's weights and
projections (``weights.make``) and the benchmark's batches, and follows
the published descriptions:

* Mistral (HF ``MistralForCausalLM``): pre-norm RMSNorm blocks, GQA
  attention with rotate-half RoPE, causal softmax over all keys, SwiGLU MLP.
* Mamba-2 (HF ``Mamba2Mixer``, ``norm_before_gate=False``): in_proj ->
  depthwise causal conv + SiLU -> the SSM in its naive quadratic form
  y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<u<=t} dt_u A) dt_s x_s + D x_t
  -> RMSNorm(y * SiLU(z)) -> out_proj.
* Loss: mean next-token cross entropy over the true vocabulary.
* Every low-rank leaf W is used as W + V B^T; the trainables are every B
  and every other leaf; the optimizer is Adam with global-norm clipping
  (decoupled weight decay on matrices), B starting at zero.

Every matmul runs at ``precision=HIGHEST``.  ``quant`` replaces that with
float8 matmuls (e4m3 operands, e5m2 gradients, per-tensor scales): the
control, one precision below the configuration's bf16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _fp8(a, dtype=jnp.float8_e4m3fn):
    """Round to float8 with one per-tensor scale (max |a| to the top of
    the format)."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
    return (a / scale).astype(dtype).astype(F32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein_fp8(spec, a, b):
    """An fp8 matmul as fp8 training does it: operands in e4m3, the
    incoming gradient in e5m2, each with a per-tensor scale; fp32 sums."""
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HI)


def _ein_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return jnp.einsum(spec, qa, qb, precision=HI), (qa, qb)


def _ein_fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HI),
                     qa, qb)
    return vjp(_fp8(g, jnp.float8_e5m2))


_ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


def _ein(spec, a, b, quant):
    a, b = a.astype(F32), b.astype(F32)
    if quant:
        return _ein_fp8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(F32)


def _eff(w, v, b):
    """W + V B^T in fp32 (one layer's leaf)."""
    return w.astype(F32) + jnp.einsum("kr,nr->kn", v.astype(F32), b,
                                      precision=HI)


def _proj(x, w, v, b, quant):
    return _ein("...k,kn->...n", x, _eff(w, v, b), quant)


# ---------------------------------------------------------------------------
# Mistral block
# ---------------------------------------------------------------------------

def _rope(x, theta):
    """Rotate-half RoPE. x: (B, S, H, D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, quant, q_block=512):
    """Causal softmax attention, one block of queries at a time."""
    B, S, Hq, D = q.shape
    rep = Hq // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = min(q_block, S)
    nb = S // qb
    qs = q.reshape(B, nb, qb, Hq, D).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = _ein("bqhd,bkhd->bhqk", qi, k, quant) / math.sqrt(D)
        qpos = i * qb + jnp.arange(qb)
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("bhqk,bkhd->bqhd", p, v, quant)

    out = jax.lax.map(block, (jnp.arange(nb), qs))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, Hq, D)


def _mistral_layer(h, lw, lv, lb, m, quant):
    B, S, _ = h.shape
    dh, hq, hkv = m["head_dim"], m["heads"], m["kv_heads"]

    def P(x, name):
        grp = "mlp" if name.startswith("w_") else "attn"
        return _proj(x, lw[grp][name], lv[grp][name], lb[grp][name], quant)

    x = _rms(h, lw["ln1"], m["eps"])
    q = _rope(P(x, "wq").reshape(B, S, hq, dh), m["theta"])
    k = _rope(P(x, "wk").reshape(B, S, hkv, dh), m["theta"])
    v = P(x, "wv").reshape(B, S, hkv, dh)
    h = h + P(_attention(q, k, v, quant).reshape(B, S, hq * dh), "wo")
    x = _rms(h, lw["ln2"], m["eps"])
    return h + P(jax.nn.silu(P(x, "w_gate")) * P(x, "w_up"), "w_down")


# ---------------------------------------------------------------------------
# Mamba-2 block
# ---------------------------------------------------------------------------

def _ssm(x, dt, a, bm, cm, quant, q_block=128):
    """Naive quadratic SSM. x (B,S,H,P), dt (B,S,H), a (H,), bm/cm
    (B,S,G,N).  One block of query positions at a time."""
    B, S, H, P = x.shape
    G = bm.shape[2]
    cum = jnp.cumsum(dt * a, axis=1)                       # (B, S, H)
    qb = min(q_block, S)
    nb = S // qb
    xdt = x * dt[..., None]

    @jax.checkpoint
    def block(i):
        t0 = i * qb
        ct = jax.lax.dynamic_slice_in_dim(cm, t0, qb, axis=1)
        cum_t = jax.lax.dynamic_slice_in_dim(cum, t0, qb, axis=1)
        cb = _ein("btgn,bsgn->bgts", ct, bm, quant)        # (B,G,qb,S)
        cb = jnp.repeat(cb, H // G, axis=1)                 # (B,H,qb,S)
        tpos = t0 + jnp.arange(qb)
        mask = jnp.arange(S)[None, :] <= tpos[:, None]
        seg = cum_t.transpose(0, 2, 1)[..., :, None] - \
            cum.transpose(0, 2, 1)[..., None, :]            # (B,H,qb,S)
        decay = jnp.exp(jnp.where(mask[None, None], seg, -jnp.inf))
        return _ein("bhts,bshp->bthp", cb * decay, xdt, quant)

    y = jax.lax.map(block, jnp.arange(nb))                  # (nb,B,qb,H,P)
    return y.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)


def _conv(x, w, b):
    """Depthwise causal conv. x (B,S,C), w (K,C), b (C,)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, k:k + x.shape[1]] * w[k].astype(F32)
               for k in range(K)) + b.astype(F32)


def _mamba_layer(h, lw, lv, lb, m, quant):
    B, S, _ = h.shape
    d_in, H, P, G, N = m["d_inner"], m["heads"], m["head_dim"], \
        m["groups"], m["state"]
    s = lw["ssm"]
    x = _rms(h, lw["ln1"], m["eps"])
    zxbcdt = _proj(x, s["in_proj"], lv["ssm"]["in_proj"],
                   lb["ssm"]["in_proj"], quant)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:2 * d_in + 2 * G * N]
    dt = jax.nn.softplus(zxbcdt[..., 2 * d_in + 2 * G * N:]
                         + s["dt_bias"].astype(F32))
    xbc = jax.nn.silu(_conv(xbc, s["conv_w"], s["conv_b"]))
    xs = xbc[..., :d_in].reshape(B, S, H, P)
    bm = xbc[..., d_in:d_in + G * N].reshape(B, S, G, N)
    cm = xbc[..., d_in + G * N:].reshape(B, S, G, N)
    a = -jnp.exp(s["a_log"].astype(F32))
    y = _ssm(xs, dt, a, bm, cm, quant) + xs * s["d_skip"].astype(F32)[:, None]
    y = _rms(y.reshape(B, S, d_in) * jax.nn.silu(z), s["norm"], m["eps"])
    return h + _proj(y, s["out_proj"], lv["ssm"]["out_proj"],
                     lb["ssm"]["out_proj"], quant)


# ---------------------------------------------------------------------------
# Loss, step
# ---------------------------------------------------------------------------

def model_dims(cfg) -> dict:
    """What the reference reads of a configuration."""
    d = dict(family=cfg.family, eps=float(cfg.norm_eps), vocab=cfg.vocab_size)
    if cfg.family == "ssm":
        d.update(d_inner=cfg.ssm_expand * cfg.d_model,
                 head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
                 groups=max(1, cfg.ssm_groups),
                 heads=cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim)
    else:
        d.update(heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                 head_dim=cfg.head_dim or cfg.d_model // cfg.num_heads,
                 theta=float(cfg.rope_theta))
    return d


def _nest(flat: dict):
    """{"['a']['b']": x} -> {"a": {"b": x}}."""
    out: dict = {}
    for path, x in flat.items():
        keys = [k.strip("'") for k in path.strip("[]").split("][")]
        node = out
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = x
    return out


def loss_fn(weights: dict, vs: dict, bs: dict, tokens, labels, m: dict,
            quant: bool = False):
    """Mean CE. ``weights``/``vs``/``bs``: flat {path: array}; the dense
    trainables are in ``weights`` (float32), the B in ``bs``."""
    W, V, Bt = _nest(weights), _nest(vs), _nest(bs)
    layer = _mamba_layer if m["family"] == "ssm" else _mistral_layer
    h = W["embed"]["tok"].astype(F32)[tokens]

    def body(h, xs):
        lw, lv, lb = xs
        return jax.checkpoint(
            lambda h_: layer(h_, lw, lv, lb, m, quant))(h), None

    lv = V.get("layers", {})
    lb = Bt.get("layers", {})
    h, _ = jax.lax.scan(body, h, (W["layers"], lv, lb))
    h = _rms(h, W["final_norm"], m["eps"])
    un = (_eff(W["unembed"], V["unembed"], Bt["unembed"]) if "unembed" in V
          else W["unembed"].astype(F32))[:, :m["vocab"]]
    B, S, d = h.shape
    rows = h.reshape(-1, d)
    lab = labels.reshape(-1)
    chunk = min(rows.shape[0], 2048)

    @jax.checkpoint
    def ce(args):
        hr, yr = args
        lg = _ein("td,dv->tv", hr, un, quant)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, yr[:, None], 1)[:, 0])

    n = rows.shape[0] // chunk
    tot = jax.lax.map(ce, (rows.reshape(n, chunk, d), lab.reshape(n, chunk)))
    return jnp.sum(tot) / rows.shape[0]


def optimizer(tcfg) -> dict:
    """The optimizer settings the reference follows (constant LR only: a
    traffic with a schedule needs the schedule here first)."""
    if tcfg.schedule != "constant":
        raise ValueError(f"the reference has no {tcfg.schedule!r} schedule")
    return dict(lr=tcfg.lr, beta1=tcfg.beta1, beta2=tcfg.beta2, eps=tcfg.eps,
                wd=tcfg.weight_decay, clip=tcfg.grad_clip)


def _norms(tree: dict) -> dict:
    """Per-layer (layered leaves) or whole-leaf fp32 norms."""
    out = {}
    for path, x in tree.items():
        x = x.astype(F32)
        axes = (tuple(range(1, x.ndim)) if path.startswith("['layers']")
                else tuple(range(x.ndim)))
        out[path] = jnp.sqrt(jnp.sum(x * x, axis=axes))
    return out


def run(weights: dict, vs: dict, lowrank: dict, batches, m: dict, opt: dict,
        quant: bool = False, half_batch: bool = False) -> dict:
    """Three (or ``len(batches)``) optimizer steps from B = 0.

    ``weights``: flat {path: stored array}; ``lowrank``: {path: (k, n, r)}.
    Returns the losses, the per-leaf norms of the first clipped gradient
    and of each trainable's change after the last step.
    """
    dense = {p: w for p, w in weights.items() if p not in lowrank}
    frozen = {p: weights[p] for p in lowrank}
    bs = {p: jnp.zeros(weights[p].shape[:-2] + (n, r), F32)
          for p, (_, n, r) in lowrank.items()}
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], opt["wd"]

    @jax.jit
    def step(frozen, vs, train, mom, tokens, labels, t, lr):
        def f(tr):
            ws = dict(frozen)
            ws.update({p: x for p, x in tr["dense"].items()})
            return loss_fn(ws, vs, tr["b"], tokens, labels, m, quant)

        loss, g = jax.value_and_grad(f)(
            {"dense": {p: x.astype(F32) for p, x in train["dense"].items()},
             "b": train["b"]})
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["clip"] / jnp.maximum(gn, 1e-9)) \
            if opt["clip"] else 1.0
        g = jax.tree.map(lambda x: x * scale, g)
        mom = jax.tree.map(lambda mm, x: (b1 * mm[0] + (1 - b1) * x,
                                          b2 * mm[1] + (1 - b2) * x * x),
                           mom, g, is_leaf=lambda x: isinstance(x, tuple))

        def upd(p, mv, decay):
            mh = mv[0] / (1 - b1 ** t)
            vh = mv[1] / (1 - b2 ** t)
            p32 = p.astype(F32)
            delta = mh / (jnp.sqrt(vh) + eps)
            if wd and decay:
                delta = delta + wd * p32
            return (p32 - lr * delta).astype(p.dtype)

        new = {"dense": {p: upd(x, mom["dense"][p], x.ndim >= 2)
                         for p, x in train["dense"].items()},
               "b": {p: upd(x, mom["b"][p], True)
                     for p, x in train["b"].items()}}
        return loss, new, mom, _norms({**g["dense"], **g["b"]})

    train = {"dense": dense, "b": bs}
    mom = jax.tree.map(lambda x: (jnp.zeros(x.shape, F32),
                                  jnp.zeros(x.shape, F32)), train)
    losses, grads = [], None
    for t, batch in enumerate(batches, start=1):
        tokens, labels = batch["tokens"], batch["labels"]
        if half_batch:
            tokens = tokens[:tokens.shape[0] // 2]
            labels = labels[:labels.shape[0] // 2]
        loss, train, mom, gnorms = step(
            frozen, vs, train, mom, tokens, labels, jnp.float32(t),
            jnp.float32(opt["lr"]))
        losses.append(float(loss))
        if grads is None:
            grads = {p: np.asarray(x) for p, x in gnorms.items()}
    change = {p: train["dense"][p].astype(F32) - dense[p].astype(F32)
              for p in dense}
    change.update(train["b"])
    return {"losses": losses, "grads": grads,
            "change": {p: np.asarray(x) for p, x in _norms(change).items()}}
