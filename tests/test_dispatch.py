"""Kernel dispatch layer tests.

Covers the ISSUE-1 acceptance criteria:
  * ragged (non-multiple-of-128) shapes agree with kernels/ref.py on BOTH
    the padded-Pallas(interpret) route and the XLA fallback route;
  * the fused backward matches jax.grad of the reference forward to fp32
    tolerance;
  * lowrank_matmul fwd+bwd, inner_update, and outer_merge_resample really
    flow through kernels/dispatch.py (verified by monkeypatching TABLE).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig
from repro.kernels import dispatch, ref
from repro.kernels.lowrank_backward import lowrank_backward as pl_backward
from repro.kernels.lowrank_forward import lowrank_forward as pl_forward
from repro.models.linear import lowrank_matmul
from repro.optim import subspace

RNG = np.random.default_rng(7)

RAGGED = [(5, 7, 9, 3), (33, 130, 65, 5), (200, 257, 96, 17)]
ALIGNED = [(128, 128, 128, 8), (256, 384, 256, 32)]


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


def _ops(m, k, n, r, dtype=jnp.float32):
    return (_arr((m, k), dtype), _arr((k, n), dtype), _arr((k, r), dtype),
            _arr((n, r), dtype))


# ---------------------------------------------------------------------------
# Ragged shapes == ref on both routes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,r", RAGGED + ALIGNED)
@pytest.mark.parametrize("route", ["pallas", "xla"])
def test_forward_matches_ref(m, k, n, r, route, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", route)
    x, w, v, b = _ops(m, k, n, r)
    y, p = dispatch.lowrank_forward(x, w, v, b, return_p=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.lowrank_forward(x, w, v, b)),
                               rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(np.asarray(p), np.asarray(x @ v),
                               rtol=2e-4, atol=2e-3)


# non-square blocks with two or more steps on every grid axis: the accp
# carry across j, the v block held after the j == 0 slab, the p write-out
@pytest.mark.parametrize("m,k,n,r,blocks", [
    (96, 512, 768, 16, (48, 256, 128)),      # grid (2, 3, 4)
    (32, 768, 256, 8, (16, 128, 384)),       # grid (2, 2, 2)
])
@pytest.mark.parametrize("return_p", [False, True])
def test_forward_kernel_non_square_blocks_match_ref(m, k, n, r, blocks,
                                                    return_p):
    bm, bn, bk = blocks
    x, w, v, b = _ops(m, k, n, r)
    out = pl_forward(x, w, v, b, bm=bm, bn=bn, bk=bk, interpret=True,
                     return_p=return_p)
    y, p = out if return_p else (out, None)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.lowrank_forward(x, w, v, b)),
                               rtol=2e-4, atol=2e-3)
    if return_p:
        np.testing.assert_allclose(np.asarray(p), np.asarray(x @ v),
                                   rtol=2e-4, atol=2e-3)


# (M, K, N, r) -> the forward's (bm, bn, bk): the NeMo-12B q, k/v, o,
# gate/up, down and unembedding chunk; llama-100m and qwen2-7b as
# tests/test_tpu_compile.py compiles them; a decode row block; a ragged one
FWD_PICKS = {
    (8192, 5120, 4096, 128): (512, 1024, 1024),
    (8192, 5120, 1024, 128): (512, 1024, 1024),
    (8192, 4096, 5120, 128): (512, 1024, 1024),
    (8192, 5120, 14336, 128): (512, 1024, 1024),
    (8192, 14336, 5120, 128): (512, 1024, 1024),
    (1024, 5120, 16384, 128): (512, 1024, 1024),
    (16384, 640, 640, 128): (512, 640, 640),
    (16384, 640, 1792, 128): (512, 896, 640),
    (16384, 1792, 640, 128): (512, 640, 896),
    (16384, 640, 32256, 128): (512, 896, 640),
    (16, 640, 32256, 128): (16, 896, 640),
    (4096, 3584, 18944, 128): (512, 512, 896),
    (16, 5120, 14336, 128): (16, 1024, 1024),
    (33, 130, 650, 5): (48, 768, 256),
}


@pytest.mark.parametrize("shape", sorted(FWD_PICKS))
def test_forward_blocks_fit_the_padded_shape(shape):
    m, k, n, r = shape
    sizes = (2.0,) * 4      # bf16 operands
    bm, mp, bn, np_, bk, kp = dispatch._fwd_blocks(m, k, n, r, sizes)
    assert (bm, bn, bk) == FWD_PICKS[shape]
    # the same padding as _blocks gives every op, and blocks that tile it
    _, mp0, _, np0, _, kp0 = dispatch._blocks(m, n, k)
    assert (mp, np_, kp) == (mp0, np0, kp0)
    assert mp % bm == 0 and np_ % bn == 0 and kp % bk == 0
    assert bm % dispatch.SUBLANE == 0
    assert bn % dispatch.LANE == 0 and bk % dispatch.LANE == 0
    assert dispatch._fwd_vmem_bytes(m, k, n, r, sizes) \
        <= dispatch.VMEM_BUDGET


def test_forward_blocks_fall_back_when_nothing_fits(monkeypatch):
    # no block fits the budget: the 128-blocks stay, and the guard that
    # sizes them sends the shape to XLA
    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    m, k, n, r = 8192, 5120, 14336, 128
    assert dispatch.route("lowrank_forward", shapes=(m, k, n, r),
                          dtypes=(jnp.bfloat16,) * 4) == "pallas"
    monkeypatch.setattr(dispatch, "VMEM_BUDGET", 2 ** 16)
    assert dispatch._fwd_blocks(m, k, n, r, (2.0,) * 4) == \
        tuple(dispatch._blocks(m, n, k))
    assert dispatch.route("lowrank_forward", shapes=(m, k, n, r),
                          dtypes=(jnp.bfloat16,) * 4) == "xla"


@pytest.mark.parametrize("m,k,n,r", RAGGED + ALIGNED)
@pytest.mark.parametrize("route", ["pallas", "xla"])
def test_backward_matches_ref(m, k, n, r, route, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", route)
    _, w, v, b = _ops(m, k, n, r)
    dy, p = _arr((m, n)), _arr((m, r))
    dx, db = dispatch.lowrank_backward(dy, w, v, b, p)
    np.testing.assert_allclose(
        np.asarray(dx), np.asarray(dy @ w.T + (dy @ b) @ v.T),
        rtol=2e-4, atol=5e-3)
    np.testing.assert_allclose(np.asarray(db),
                               np.asarray(dy).T @ np.asarray(p),
                               rtol=2e-4, atol=5e-3)


@pytest.mark.parametrize("m,k,n,r", [(33, 130, 650, 5), (64, 128, 384, 16)])
def test_backward_n_chunked_matches_ref(m, k, n, r, monkeypatch):
    # a budget that holds only the smallest blocks splits N (and K, and
    # M where it can) into several blocks: dx gathered over N blocks, q
    # reused across K blocks, the resident dB gathered over the row blocks
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "pallas")
    sizes = (4,) * 5
    monkeypatch.setattr(dispatch, "VMEM_BUDGET",
                        dispatch._bwd_tile_bytes(16, 128, 128, r, sizes))
    bm, mp, bn, np_, bk, kp = dispatch._bwd_blocks(m, k, n, r, sizes)
    assert (bm, bn, bk) == (16, 128, 128) and np_ // bn >= 3
    dispatch.clear_kernel_cache()
    _, w, v, b = _ops(m, k, n, r)
    dy, p = _arr((m, n)), _arr((m, r))
    dx, db = dispatch.lowrank_backward(dy, w, v, b, p)
    (key,) = dispatch.kernel_cache_info()["keys"]
    assert key[0] == "lowrank_backward" and key[3] == (16, 128, 128)
    np.testing.assert_allclose(
        np.asarray(dx), np.asarray(dy @ w.T + (dy @ b) @ v.T),
        rtol=2e-4, atol=5e-3)
    np.testing.assert_allclose(np.asarray(db),
                               np.asarray(dy).T @ np.asarray(p),
                               rtol=2e-4, atol=5e-3)


# several blocks on every grid axis: the dx accumulator carried over the
# N blocks, q from the k == 0 sweep reused by later K blocks, b held past
# that sweep, the resident dB gathered over the row blocks and copied out
# after the last; and the edge cases of one K block, of one N block and of
# one row block
@pytest.mark.parametrize("m,k,n,r,blocks", [
    (96, 512, 768, 16, (48, 256, 128)),      # grid (2, 2, 6)
    (32, 768, 256, 8, (16, 128, 128)),       # grid (2, 6, 2)
    (48, 384, 384, 5, (16, 128, 384)),       # grid (3, 3, 1)
    (48, 128, 512, 4, (16, 128, 256)),       # grid (3, 1, 2)
    (16, 256, 640, 8, (16, 128, 128)),       # grid (1, 2, 5)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_backward_kernel_non_square_blocks_match_ref(m, k, n, r, blocks,
                                                     dtype):
    bm, bk, bn = blocks
    x, w, v, b = _ops(m, k, n, r, dtype)
    dy = _arr((m, n), dtype)
    p = (x @ v).astype(dtype)               # the forward's residual
    dx, db = pl_backward(dy, w, v, b, p, bm=bm, bk=bk, bn=bn,
                         interpret=True)
    assert dx.dtype == dtype and db.dtype == jnp.float32
    assert dx.shape == (m, k) and db.shape == (n, r)
    # the VJP of the reference forward, in fp32
    f32 = [a.astype(jnp.float32) for a in (x, w, v, b, dy)]
    _, vjp = jax.vjp(lambda x_, b_: ref.lowrank_forward(x_, f32[1], f32[2],
                                                        b_), f32[0], f32[3])
    want_dx, want_db = vjp(f32[4])
    rel = 1e-5 if dtype == jnp.float32 else 1e-2
    for got, want in ((dx, want_dx), (db, want_db)):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=0, atol=rel * np.abs(want).max())


# (M, K, N, r) -> the backward's (bm, bn, bk), bn blocking the
# contraction N of dx: the NeMo-12B q, k/v, o, gate/up, down and
# unembedding chunk; qwen2-7b's down-projection; llama-100m's unembedding
# chunk; a decode row block and a ragged shape
BWD_PICKS = {
    (8192, 5120, 4096, 128): (512, 1024, 1024),
    (8192, 5120, 1024, 128): (512, 1024, 1024),
    (8192, 4096, 5120, 128): (512, 1024, 1024),
    (8192, 5120, 14336, 128): (512, 1024, 1024),
    (8192, 14336, 5120, 128): (512, 1024, 1024),
    (1024, 5120, 16384, 128): (512, 1024, 1024),
    (4096, 18944, 3584, 128): (512, 896, 512),
    (16384, 640, 32256, 128): (512, 896, 640),
    (16, 5120, 14336, 128): (16, 1024, 1024),
    (33, 130, 650, 5): (48, 768, 256),
}


@pytest.mark.parametrize("shape", sorted(BWD_PICKS))
def test_backward_blocks_fit_the_padded_shape(shape, monkeypatch):
    m, k, n, r = shape
    sizes = (2.0,) * 5      # bf16 operands
    bm, mp, bn, np_, bk, kp = dispatch._bwd_blocks(m, k, n, r, sizes)
    assert (bm, bn, bk) == BWD_PICKS[shape]
    _, mp0, _, np0, _, kp0 = dispatch._blocks(m, n, k)
    assert (mp, np_, kp) == (mp0, np0, kp0)     # N 5120 stays 5120
    assert mp % bm == 0 and np_ % bn == 0 and kp % bk == 0
    assert bm % dispatch.SUBLANE == 0
    assert bn % dispatch.LANE == 0 and bk % dispatch.LANE == 0
    assert dispatch._bwd_vmem_bytes(m, k, n, r, sizes) \
        <= dispatch.VMEM_BUDGET
    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert dispatch.route("lowrank_backward", shapes=shape,
                          dtypes=(jnp.bfloat16,) * 5) == "pallas"


@pytest.mark.parametrize("n,route", [(152064, "pallas"), (262144, "xla")])
def test_backward_resident_db_bounds_the_width(n, route, monkeypatch):
    # every block fits at any width; what grows with N is the resident
    # fp32 dB (N, r): qwen2-7b's whole vocabulary (74.25 MiB) keeps the
    # kernel, a dB over BWD_DB_VMEM takes XLA
    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    shape = (1024, 3584, n, 128)
    assert dispatch._bwd_vmem_bytes(*shape, (2.0,) * 5) \
        <= dispatch.VMEM_BUDGET
    assert (4 * n * 128 <= dispatch.BWD_DB_VMEM) == (route == "pallas")
    assert dispatch.route("lowrank_backward", shapes=shape,
                          dtypes=(jnp.bfloat16,) * 5) == route


def test_route_log_records_each_choice(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "xla")
    dispatch.clear_kernel_cache()
    x, w, v, b = _ops(8, 16, 24, 2)
    dispatch.lowrank_forward(x, w, v, b)
    assert dispatch.route_log() == {
        ("lowrank_forward", (8, 16, 24, 2)): "xla"}
    dispatch.clear_kernel_cache()
    assert dispatch.route_log() == {}


@pytest.mark.parametrize("m,k,n,r", [(40, 50, 60, 6), (128, 256, 128, 16)])
@pytest.mark.parametrize("route", ["pallas", "xla"])
def test_merge_project_adam_ragged(m, k, n, r, route, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", route)
    _, w, v, b = _ops(m, k, n, r)
    np.testing.assert_allclose(
        np.asarray(dispatch.lowrank_merge(w, v, b)),
        np.asarray(ref.lowrank_merge(w, v, b)), rtol=2e-4, atol=2e-3)
    g = _arr((k, n))
    np.testing.assert_allclose(
        np.asarray(dispatch.lowrank_project(g, v[:, :r])),
        np.asarray(ref.lowrank_project(g, v[:, :r])), rtol=2e-4, atol=2e-3)
    bb, gg = _arr((n, r)), _arr((n, r))
    mm, vv = jnp.abs(_arr((n, r), scale=0.1)), jnp.abs(_arr((n, r),
                                                           scale=0.01))
    got = dispatch.subspace_adam(bb, gg, mm, vv, lr=1e-3, step=5.0, wd=0.01)
    want = ref.subspace_adam(bb, gg, mm, vv, lr=1e-3, beta1=0.9, beta2=0.999,
                             eps=1e-8, wd=0.01, step=5.0)
    for a, c in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-5)


def test_merge_stacked_experts_both_routes(monkeypatch):
    """3-D (E, k, n) leaves merge correctly on the vmapped pallas route."""
    w = _arr((3, 24, 40))
    v = _arr((3, 24, 4))
    b = _arr((3, 40, 4))
    want = np.asarray(w) + np.einsum("ekr,enr->ekn", np.asarray(v),
                                     np.asarray(b))
    for route in ("pallas", "xla"):
        monkeypatch.setenv("REPRO_KERNEL_DISPATCH", route)
        np.testing.assert_allclose(np.asarray(dispatch.lowrank_merge(w, v, b)),
                                   want, rtol=2e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# Fused backward == jax.grad of the reference forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,r", [(33, 65, 40, 5), (128, 128, 128, 16)])
@pytest.mark.parametrize("route", ["pallas", "xla"])
def test_custom_vjp_matches_autodiff_of_ref(m, k, n, r, route, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", route)
    x, w, v, b = _ops(m, k, n, r)
    co = _arr((m, n))

    def f_disp(x, b):
        return jnp.sum(lowrank_matmul(x, w, b, v) * co)

    def f_ref(x, b):
        return jnp.sum((x @ w + (x @ v) @ b.T) * co)

    gx1, gb1 = jax.grad(f_disp, argnums=(0, 1))(x, b)
    gx2, gb2 = jax.grad(f_ref, argnums=(0, 1))(x, b)
    np.testing.assert_allclose(np.asarray(gb1), np.asarray(gb2),
                               rtol=2e-4, atol=5e-3)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=2e-4, atol=5e-3)


def test_custom_vjp_batched_leading_dims(monkeypatch):
    """(B, S, d) activations: leading dims flattened for the kernel and the
    dB contraction covers every batch/seq axis."""
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "pallas")
    B, S, k, n, r = 2, 9, 12, 10, 3
    x = _arr((B, S, k))
    w, v, b = _arr((k, n)), _arr((k, r)), _arr((n, r))
    co = _arr((B, S, n))
    gb1 = jax.grad(lambda b: jnp.sum(lowrank_matmul(x, w, b, v) * co))(b)
    gb2 = jax.grad(lambda b: jnp.sum((x @ w + (x @ v) @ b.T) * co))(b)
    np.testing.assert_allclose(np.asarray(gb1), np.asarray(gb2),
                               rtol=2e-4, atol=5e-3)


# ---------------------------------------------------------------------------
# The hot path really routes through the dispatch table
# ---------------------------------------------------------------------------

def _spy(table_entry, calls, key):
    orig = table_entry[key]

    def wrapper(*a, **kw):
        calls.append(key)
        return orig(*a, **kw)

    return wrapper


def test_lowrank_matmul_routes_through_dispatch(monkeypatch):
    calls = []
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "xla")
    monkeypatch.setitem(dispatch.TABLE["lowrank_forward"], "xla",
                        _spy(dispatch.TABLE["lowrank_forward"], calls,
                             "xla"))
    monkeypatch.setitem(dispatch.TABLE["lowrank_backward"], "xla",
                        _spy(dispatch.TABLE["lowrank_backward"], calls,
                             "xla"))
    x, w, v, b = _ops(8, 12, 10, 3)
    jax.grad(lambda b: jnp.sum(lowrank_matmul(x, w, b, v)))(b)
    assert len(calls) >= 2, "forward AND backward must go through TABLE"


def _tiny_state():
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=4,
                       lazy_k=5, lr=1e-2, warmup_steps=0, total_steps=10,
                       min_dim_for_lowrank=8, weight_decay=0.0,
                       grad_clip=0.0, schedule="constant")
    params = {"w1": _arr((16, 12)), "w2": _arr((16, 12)),
              "w3": _arr((12, 10)), "bias": _arr((12,))}
    params, state = subspace.init(params, tcfg, jax.random.key(0))
    return tcfg, params, state


def test_inner_update_routes_and_groups(monkeypatch):
    """inner_update goes through TABLE['subspace_adam'] with same-shape B
    leaves grouped into ONE stacked call."""
    calls = []
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "xla")
    orig = dispatch.TABLE["subspace_adam"]["xla"]

    def spy(b2, *a, **kw):
        calls.append(b2.shape)
        return orig(b2, *a, **kw)

    monkeypatch.setitem(dispatch.TABLE["subspace_adam"], "xla", spy)
    tcfg, params, state = _tiny_state()
    trainable = subspace.trainable_of(params, state)
    grads = jax.tree.map(jnp.ones_like, trainable)
    new_p, new_t, new_s, gn = subspace.inner_update(
        grads, trainable, params, state, lr=1e-2, tcfg=tcfg)
    # w1, w2 share B shape (12, 4) -> one stacked (2*12, 4) call;
    # w3 B is (10, 4) -> its own call; bias is dense -> no call.
    assert len(calls) == 2, calls
    assert sorted(c[0] for c in calls) == [10, 24]


def test_inner_update_matches_ref_adam(monkeypatch):
    """Grouped/batched update == the plain per-leaf Adam formula."""
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "xla")
    tcfg, params, state = _tiny_state()
    trainable = subspace.trainable_of(params, state)
    grads = jax.tree.map(
        lambda t: jnp.asarray(RNG.normal(size=t.shape), t.dtype), trainable)
    _, new_t, new_s, _ = subspace.inner_update(
        grads, trainable, params, state, lr=1e-2, tcfg=tcfg)
    old = subspace.slots_by_path(params, state)
    new = subspace.slots_by_path(params, new_s)
    paths = [subspace._path_str(p) for p, _ in jax.tree_util
             .tree_flatten_with_path(subspace.params_of(params))[0]]

    def member_grad(name):
        """The member's gradient row inside its group's stacked buffer."""
        i = paths.index(f"/{name}")
        for g, spec in enumerate(state.layout.groups):
            if i in spec.leaf_idx:
                return grads.groups[g][spec.leaf_idx.index(i)]
        raise AssertionError(name)

    for name in ("w1", "w2", "w3"):
        slot = old[f"/{name}"]
        nb, nm, nv = ref.subspace_adam(
            slot.b, member_grad(name), slot.m, slot.v, lr=1e-2,
            beta1=tcfg.beta1, beta2=tcfg.beta2, eps=tcfg.eps, wd=0.0,
            step=1.0)
        np.testing.assert_allclose(np.asarray(new[f"/{name}"].b),
                                   np.asarray(nb), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(new[f"/{name}"].m),
                                   np.asarray(nm), rtol=1e-5, atol=1e-6)


def test_outer_merge_routes_through_dispatch(monkeypatch):
    calls = []
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "xla")
    orig = dispatch.TABLE["lowrank_merge"]["xla"]

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setitem(dispatch.TABLE["lowrank_merge"], "xla", spy)
    tcfg, params, state = _tiny_state()
    trainable = subspace.trainable_of(params, state)
    grads = jax.tree.map(jnp.ones_like, trainable)
    _, _, state, _ = subspace.inner_update(grads, trainable, params, state,
                                           lr=1e-2, tcfg=tcfg)
    new_params, new_state = subspace.outer_merge_resample(params, state,
                                                          tcfg)
    params = subspace.params_of(params)
    new_params = subspace.params_of(new_params)
    # one BATCHED merge per group ({w1, w2} share a group; w3 has its own)
    assert len(calls) == len(state.groups) == 2
    # merge really applied: W' = W + V B^T
    slots = subspace.slots_by_path(params, state)
    new_slots = subspace.slots_by_path(params, new_state)
    for name in ("w1", "w2", "w3"):
        slot = slots[f"/{name}"]
        want = np.asarray(params[name]) + np.asarray(
            slot.proj) @ np.asarray(slot.b).T
        np.testing.assert_allclose(np.asarray(new_params[name]), want,
                                   rtol=1e-4, atol=1e-5)
        assert float(jnp.abs(new_slots[f"/{name}"].b).sum()) == 0.0


# ---------------------------------------------------------------------------
# Route selection
# ---------------------------------------------------------------------------

def test_route_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "pallas")
    assert dispatch.route("lowrank_forward",
                          shapes=(8, 8, 8, 2)) == "pallas"
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "xla")
    assert dispatch.route("lowrank_backward",
                          shapes=(128, 128, 128, 8)) == "xla"
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "palas")  # typo: fail loudly
    with pytest.raises(ValueError, match="REPRO_KERNEL_DISPATCH"):
        dispatch.route("lowrank_forward", shapes=(8, 8, 8, 2))


def test_route_on_multi_device_mesh_is_xla(monkeypatch):
    # Mosaic kernels cannot be partitioned by the compiler: under a mesh
    # of more than one device every op takes the XLA schedule
    from repro.sharding import ctx as shard_ctx

    class _Mesh:
        size, shape = 4, {"data": 2, "model": 2}

    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    shapes = (128, 128, 128, 8)
    assert dispatch.route("lowrank_forward", shapes=shapes) == "pallas"
    monkeypatch.setattr(shard_ctx, "_MESH", _Mesh())
    assert dispatch.route("lowrank_forward", shapes=shapes) == "xla"
    assert dispatch.route("subspace_adam") == "xla"


def test_route_auto_cpu_prefers_xla(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    if jax.default_backend() != "tpu":
        assert dispatch.route("lowrank_forward",
                              shapes=(128, 128, 128, 8)) == "xla"


def test_bf16_pallas_route(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "pallas")
    x, w, v, b = _ops(24, 33, 40, 4, jnp.bfloat16)
    y = dispatch.lowrank_forward(x, w, v, b)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y, np.float32),
        np.asarray(ref.lowrank_forward(x, w, v, b), np.float32),
        rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# Attention: the flash kernel where the call allows it, XLA elsewhere
# ---------------------------------------------------------------------------

NEMO_ATTN = (2, 4096, 32, 8, 128)     # (B, S, Hq, Hkv, D) of a NeMo-12B step

# case -> ((B, S, Hq, Hkv, D), Dv or None, blockwise_attention kwargs,
#          on a mesh of 4 devices, route)
ATTN_ROUTES = {
    "nemo_train": (NEMO_ATTN, None, {}, False, "pallas"),
    "not_causal": (NEMO_ATTN, None, {"causal": False}, False, "xla"),
    "q_offset": (NEMO_ATTN, None, {"q_offset": 128}, False, "xla"),
    "kv_valid_len": (NEMO_ATTN, None, {"kv_valid_len": 4000}, False,
                     "xla"),
    "head_64": ((2, 4096, 32, 8, 64), None, {}, False, "xla"),
    "mla_192_v128": ((2, 4096, 16, 16, 192), 128, {}, False, "xla"),
    "seq_1500": ((2, 1500, 32, 8, 128), None, {}, False, "xla"),
    "mesh": (NEMO_ATTN, None, {}, True, "xla"),
    "prefill": (NEMO_ATTN, None, {"prefill": True}, False, "xla"),
}


@pytest.fixture
def on_tpu(monkeypatch):
    """The backend reads as a TPU for the route; the flash kernels built
    for it (compiled, not interpreted) are dropped after the test."""
    from repro.kernels import flash_attention as fa

    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    dispatch.clear_kernel_cache()
    yield
    fa._kernel.cache_clear()


@pytest.mark.parametrize("case", sorted(ATTN_ROUTES))
def test_attention_route(case, monkeypatch, on_tpu):
    from repro.models.attention import blockwise_attention
    from repro.sharding import ctx as shard_ctx

    class _Mesh:
        size, shape = 4, {"data": 2, "model": 2}

    shapes, dv, kw, on_mesh, want = ATTN_ROUTES[case]
    b, s, hq, hkv, d = shapes
    if on_mesh:
        monkeypatch.setattr(shard_ctx, "_MESH", _Mesh())
    if "kv_valid_len" in kw:
        kw = {**kw, "kv_valid_len": jnp.asarray(kw["kv_valid_len"])}
    args = [jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in
            ((b, s, hq, d), (b, s, hkv, d), (b, s, hkv, dv or d))]
    # the route is taken at trace time: trace, compile nothing
    out = jax.eval_shape(lambda q, k, v: blockwise_attention(q, k, v, **kw),
                         *args)
    assert out.shape == (b, s, hq, dv or d)
    assert dispatch.route_log() == {("attention", shapes): want}


# (the model call, the attention route it takes) at a D-128 bf16 model:
# training forwards take the kernel, a serving prefill of the same shape
# (a 128-token prompt from position 0) keeps XLA
MODEL_ATTN = {"forward": "pallas", "prefill": "xla"}


@pytest.mark.parametrize("call", sorted(MODEL_ATTN))
def test_model_attention_route(call, on_tpu):
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("mistral-nemo-12b").reduced().replace(
        head_dim=128, dtype="bfloat16", param_dtype="bfloat16")
    b, s = 2, 128
    params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if call == "forward":
        jax.eval_shape(lambda p, t: lm.forward_hidden(p, t, cfg)[0],
                       params, tokens)
    else:
        state = jax.eval_shape(lambda: lm.alloc_decode_state(cfg, b, s))
        jax.eval_shape(lambda p, t, st: lm.prefill(p, t, cfg, st),
                       params, tokens, state)
    shape = (b, s, cfg.num_heads, cfg.num_kv_heads, 128)
    assert {k: v for k, v in dispatch.route_log().items()
            if k[0] == "attention"} == {("attention", shape): MODEL_ATTN[call]}
