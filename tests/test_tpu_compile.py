"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel body in Python, so it cannot see what the
chip's compiler refuses: a block that breaks the (8, 128) tiling, or a
working set larger than the kernel's scoped VMEM.  Here every kernel the
training and serving paths reach is compiled (not run) by the TPU compiler
for one chip of a described ``v5e:2x2`` topology, at the padded shapes the
dispatch layer hands it for llama-100m (12 x d640 / ff1712, vocab 32128,
r=128, 64 x 256 tokens per step) and at the widest qwen2-7b shape the VMEM
guard admits.  Each case asserts that the program holds a
``tpu_custom_call`` — the compiled kernel, not an XLA fallback.

This is the only file that describes the topology.  The description loads
the TPU library, which one process at a time may hold, so it happens in a
module-scoped fixture (never at import): under several test workers only
the worker given this file loads it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.lowrank_backward import lowrank_backward
from repro.kernels.lowrank_forward import lowrank_forward
from repro.kernels.lowrank_update import lowrank_merge, lowrank_merge_sr
from repro.kernels.subspace_adam import subspace_adam, subspace_adam_q8

BF16, F32, I8, U32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.uint32
TOKENS = 64 * 256       # llama-100m rows per step: batch 64 x seq 256
RANK = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *specs) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


def _fwd_specs(m, k, n, r=RANK):
    return ((m, k), BF16), ((k, n), BF16), ((k, r), BF16), ((n, r), BF16)


# (M, K, N): the llama-100m q/k/v/o, up/gate, down and unembedding widths
# as the dispatch layer pads them, and qwen2-7b's widest up-projection
FWD = {
    "llama100m_attn": (TOKENS, 640, 640),
    "llama100m_up": (TOKENS, 640, 1792),
    "llama100m_down": (TOKENS, 1792, 640),
    "llama100m_unembed": (TOKENS, 640, 32256),
    # serving prefill: one prompt's last position through the unembedding
    "llama100m_prefill_logits": (16, 640, 32256),
    "qwen2_7b_up": (4096, 3584, 18944),
}


@pytest.mark.parametrize("case", sorted(FWD))
def test_lowrank_forward_compiles(case, one_chip):
    m, k, n = FWD[case]
    assert dispatch._fwd_vmem_bytes(m, k, n, RANK, (2,) * 4) \
        <= dispatch.VMEM_BUDGET
    hlo = _compile(lambda x, w, v, b: lowrank_forward(x, w, v, b,
                                                      return_p=True),
                   one_chip, *_fwd_specs(m, k, n))
    assert "tpu_custom_call" in hlo


BWD = {
    "llama100m_up": (TOKENS, 640, 1792),
    "llama100m_down": (TOKENS, 1792, 640),
    "llama100m_unembed": (TOKENS, 640, 32256),
    "qwen2_7b_up": (4096, 3584, 18944),
}


@pytest.mark.parametrize("case", sorted(BWD))
def test_lowrank_backward_compiles(case, one_chip, monkeypatch):
    m, k, n = BWD[case]
    # the guard admits the shape on the chip, with the N-chunking it
    # picks; the compiler must then accept exactly that kernel
    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert dispatch.route("lowrank_backward", shapes=(m, k, n, RANK),
                          dtypes=(BF16,) * 5) == "pallas"
    chunks = dispatch._bwd_chunks(m, k, n, RANK, (2,) * 5)
    n = dispatch._round_up(n, chunks * 128)
    monkeypatch.undo()
    hlo = _compile(lambda dy, w, v, b, p: lowrank_backward(
        dy, w, v, b, p, n_chunks=chunks), one_chip,
        ((m, n), BF16), ((k, n), BF16), ((k, RANK), BF16),
        ((n, RANK), BF16), ((m, RANK), BF16))
    assert "tpu_custom_call" in hlo


def test_lowrank_backward_refuses_what_cannot_fit(monkeypatch):
    # qwen2-7b's down-projection: even a 128-wide N chunk leaves the
    # 18944-wide dx strip over budget, so the guard keeps it on XLA
    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert dispatch.route("lowrank_backward",
                          shapes=(4096, 18944, 3584, RANK),
                          dtypes=(BF16,) * 5) == "xla"


# subspace Adam rows: the llama-100m MLP group (3 x 12 layers x 1712
# padded) and the unembedding's 32256 rows, lane-dense at r = 128
@pytest.mark.parametrize("rows", [41216, 32256])
def test_subspace_adam_compiles(rows, one_chip):
    s = (rows, RANK)
    hlo = _compile(lambda b, g, m, v: subspace_adam(
        b, g, m, v, lr=1e-3, step=3.0, wd=0.05), one_chip,
        (s, F32), (s, F32), (s, F32), (s, F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("master", ["float32", "bfloat16"])
def test_subspace_adam_q8_compiles(master, one_chip):
    s, sc = (41216, 128), (41216, 1)
    sr = master == "bfloat16"

    def fn(b, g, mq, ms, vq, vs, bits):
        return subspace_adam_q8(b, g, mq, ms, vq, vs, lr=1e-3, step=3.0,
                                wd=0.05, bits=bits if sr else None)

    hlo = _compile(fn, one_chip, (s, jnp.dtype(master)), (s, F32), (s, I8),
                   (sc, F32), (s, I8), (sc, F32), (s, U32))
    assert "tpu_custom_call" in hlo


# merge shapes: (K, N) of the grouped weight as padded to 256-blocks
MERGE = {"llama100m_up": (768, 1792), "llama100m_down": (1792, 768),
         "llama100m_unembed": (768, 32256)}


@pytest.mark.parametrize("case", sorted(MERGE))
def test_lowrank_merge_compiles(case, one_chip):
    k, n = MERGE[case]
    hlo = _compile(lowrank_merge, one_chip, ((k, n), BF16),
                   ((k, RANK), BF16), ((n, RANK), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("case", sorted(MERGE))
def test_lowrank_merge_sr_compiles(case, one_chip):
    k, n = MERGE[case]
    hlo = _compile(lowrank_merge_sr, one_chip, ((k, n), BF16),
                   ((k, RANK), BF16), ((n, RANK), BF16), ((k, n), U32))
    assert "tpu_custom_call" in hlo


def test_lowrank_batch_forward_compiles(one_chip):
    # serving prefill with one adapter per row: 4 rows of 128 tokens
    # through the vmapped 2-D kernel against the llama-100m up-projection
    rows, s, k, n = 4, 128, 640, 1792

    def fn(x, w, v, b):
        return jax.vmap(lambda x2, b2: lowrank_forward(x2, w, v, b2))(x, b)

    hlo = _compile(fn, one_chip, ((rows, s, k), BF16), ((k, n), BF16),
                   ((k, RANK), BF16), ((rows, n, RANK), BF16))
    assert "tpu_custom_call" in hlo
