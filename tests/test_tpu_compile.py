"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel body in Python, so it cannot see what the
chip's compiler refuses: a block that breaks the (8, 128) tiling, or a
working set larger than the kernel's scoped VMEM.  Here every kernel the
training and serving paths reach is compiled (not run) by the TPU compiler
for one chip of a described ``v5e:2x2`` topology, at the padded shapes the
dispatch layer hands it for llama-100m (12 x d640 / ff1712, vocab 32128,
r=128, 64 x 256 tokens per step), at qwen2-7b's widest shapes and at
NeMo-12B's widths (d5120 / ff14336, 2 x 4096 tokens per step), each at the
blocks the dispatch layer picks.
Each case asserts that the program holds a ``tpu_custom_call`` — the
compiled kernel, not an XLA fallback — named after the kernel's public
function, so that a profiler trace tells the kernels apart by name.

This is the only file that describes the topology.  The description loads
the TPU library, which one process at a time may hold, so it happens in a
module-scoped fixture (never at import): under several test workers only
the worker given this file loads it.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.flash_attention import flash_attention, flash_blocks
from repro.kernels.lowrank_backward import lowrank_backward
from repro.kernels.lowrank_forward import lowrank_forward
from repro.kernels.lowrank_update import (lowrank_merge, lowrank_merge_sr,
                                          lowrank_project)
from repro.kernels.subspace_adam import (subspace_adam, subspace_adam_q8,
                                         subspace_lion, subspace_lion_q8)

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


# the instruction name of each compiled Pallas call: %<kernel>[.N] = ...
_KERNEL = re.compile(r"%([\w\-]+?)(?:\.\d+)? = [^\n]*"
                     r'custom_call_target="tpu_custom_call"')


def _assert_kernel(hlo: str, name: str) -> None:
    """The program holds the compiled kernel, and every Pallas call in it
    carries ``name`` (``vmap_<name>_`` under vmap; none is left as
    ``_lambda_``)."""
    assert "tpu_custom_call" in hlo
    found = _KERNEL.findall(hlo)
    assert found and all(name in k for k in found), found
    assert "_lambda_" not in hlo


def _fwd_specs(m, k, n, r=RANK):
    return ((m, k), BF16), ((k, n), BF16), ((k, r), BF16), ((n, r), BF16)


def _fwd_tiles(m, k, n, r=RANK):
    """The forward's (bm, bn, bk) as the dispatch layer picks them for
    bf16 operands."""
    bm, _, bn, _, bk, _ = dispatch._fwd_blocks(m, k, n, r, (2,) * 4)
    return dict(bm=bm, bn=bn, bk=bk)


NEMO_TOKENS = 2 * 4096  # NeMo-12B rows per step: batch 2 x seq 4096

# (M, K, N): the llama-100m q/k/v/o, up/gate, down and unembedding widths
# as the dispatch layer pads them, qwen2-7b's widest up-projection, and
# NeMo-12B's q, k/v, o, gate/up, down and unembedding chunk
FWD = {
    "llama100m_attn": (TOKENS, 640, 640),
    "llama100m_up": (TOKENS, 640, 1792),
    "llama100m_down": (TOKENS, 1792, 640),
    "llama100m_unembed": (TOKENS, 640, 32256),
    # serving prefill: one prompt's last position through the unembedding
    "llama100m_prefill_logits": (16, 640, 32256),
    "qwen2_7b_up": (4096, 3584, 18944),
    "nemo12b_q": (NEMO_TOKENS, 5120, 4096),
    "nemo12b_kv": (NEMO_TOKENS, 5120, 1024),
    "nemo12b_o": (NEMO_TOKENS, 4096, 5120),
    "nemo12b_gate_up": (NEMO_TOKENS, 5120, 14336),
    "nemo12b_down": (NEMO_TOKENS, 14336, 5120),
    "nemo12b_unembed_chunk": (1024, 5120, 16384),
}


@pytest.mark.parametrize("case", sorted(FWD))
def test_lowrank_forward_compiles(case, one_chip):
    # the compiler must accept the kernel at the blocks the dispatch
    # layer picks, within the working set its guard counts
    m, k, n = FWD[case]
    assert dispatch._fwd_vmem_bytes(m, k, n, RANK, (2,) * 4) \
        <= dispatch.VMEM_BUDGET
    tiles = _fwd_tiles(m, k, n)
    hlo = _compile(lambda x, w, v, b: lowrank_forward(
        x, w, v, b, return_p=True, **tiles), one_chip, *_fwd_specs(m, k, n))
    _assert_kernel(hlo, "lowrank_forward")


BWD = {
    "llama100m_up": (TOKENS, 640, 1792),
    "llama100m_down": (TOKENS, 1792, 640),
    "llama100m_unembed": (TOKENS, 640, 32256),
    "qwen2_7b_up": (4096, 3584, 18944),
    "qwen2_7b_down": (4096, 18944, 3584),
    "nemo12b_o": (NEMO_TOKENS, 4096, 5120),
    "nemo12b_gate_up": (NEMO_TOKENS, 5120, 14336),
    "nemo12b_down": (NEMO_TOKENS, 14336, 5120),
}


@pytest.mark.parametrize("case", sorted(BWD))
def test_lowrank_backward_compiles(case, one_chip, monkeypatch):
    m, k, n = BWD[case]
    # the guard admits the shape on the chip at the blocks the rule
    # picks; the compiler must then accept exactly that kernel
    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert dispatch.route("lowrank_backward", shapes=(m, k, n, RANK),
                          dtypes=(BF16,) * 5) == "pallas"
    monkeypatch.undo()
    bm, m, bn, n, bk, k = dispatch._bwd_blocks(m, k, n, RANK, (2,) * 5)
    hlo = _compile(lambda dy, w, v, b, p: lowrank_backward(
        dy, w, v, b, p, bm=bm, bk=bk, bn=bn), one_chip,
        ((m, n), BF16), ((k, n), BF16), ((k, RANK), BF16),
        ((n, RANK), BF16), ((m, RANK), BF16))
    _assert_kernel(hlo, "lowrank_backward")


def test_lowrank_backward_refuses_what_cannot_fit(monkeypatch):
    # the fused backward tiles K and N, so every width fits (qwen2-7b's
    # down-projection compiles above); what it refuses is a budget below
    # the working set of its smallest blocks
    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    shape = (4096, 18944, 3584, RANK)
    assert dispatch.route("lowrank_backward", shapes=shape,
                          dtypes=(BF16,) * 5) == "pallas"
    monkeypatch.setattr(dispatch, "VMEM_BUDGET", dispatch._bwd_tile_bytes(
        16, 128, 128, RANK, (2,) * 5) - 1)
    assert dispatch.route("lowrank_backward", shapes=shape,
                          dtypes=(BF16,) * 5) == "xla"


# subspace Adam rows: the llama-100m MLP group (3 x 12 layers x 1712
# padded) and the unembedding's 32256 rows, lane-dense at r = 128
@pytest.mark.parametrize("rows", [41216, 32256])
def test_subspace_adam_compiles(rows, one_chip):
    s = (rows, RANK)
    hlo = _compile(lambda b, g, m, v: subspace_adam(
        b, g, m, v, lr=1e-3, step=3.0, wd=0.05), one_chip,
        (s, F32), (s, F32), (s, F32), (s, F32))
    _assert_kernel(hlo, "subspace_adam")


@pytest.mark.parametrize("master", ["float32", "bfloat16"])
def test_subspace_adam_q8_compiles(master, one_chip):
    s, sc = (41216, 128), (41216, 1)
    sr = master == "bfloat16"

    def fn(b, g, mq, ms, vq, vs, bits):
        return subspace_adam_q8(b, g, mq, ms, vq, vs, lr=1e-3, step=3.0,
                                wd=0.05, bits=bits if sr else None)

    hlo = _compile(fn, one_chip, (s, jnp.dtype(master)), (s, F32), (s, I8),
                   (sc, F32), (s, I8), (sc, F32), (s, U32))
    _assert_kernel(hlo, "subspace_adam_q8")


# merge shapes: (K, N) of the grouped weight as padded to 256-blocks
MERGE = {"llama100m_up": (768, 1792), "llama100m_down": (1792, 768),
         "llama100m_unembed": (768, 32256)}


@pytest.mark.parametrize("case", sorted(MERGE))
def test_lowrank_merge_compiles(case, one_chip):
    k, n = MERGE[case]
    hlo = _compile(lowrank_merge, one_chip, ((k, n), BF16),
                   ((k, RANK), BF16), ((n, RANK), F32))
    _assert_kernel(hlo, "lowrank_merge")


@pytest.mark.parametrize("case", sorted(MERGE))
def test_lowrank_merge_sr_compiles(case, one_chip):
    k, n = MERGE[case]
    hlo = _compile(lowrank_merge_sr, one_chip, ((k, n), BF16),
                   ((k, RANK), BF16), ((n, RANK), BF16), ((k, n), U32))
    _assert_kernel(hlo, "lowrank_merge_sr")


def test_lowrank_batch_forward_compiles(one_chip):
    # serving prefill with one adapter per row: 4 rows of 128 tokens
    # through the vmapped 2-D kernel against the llama-100m up-projection
    rows, s, k, n = 4, 128, 640, 1792
    tiles = _fwd_tiles(s, k, n)

    def fn(x, w, v, b):
        return jax.vmap(lambda x2, b2: lowrank_forward(
            x2, w, v, b2, **tiles))(x, b)

    hlo = _compile(fn, one_chip, ((rows, s, k), BF16), ((k, n), BF16),
                   ((k, RANK), BF16), ((rows, n, RANK), BF16))
    _assert_kernel(hlo, "lowrank_forward")


def _attention_step(q, k, v, do):
    """Forward and backward of causal flash attention, as a step runs it."""
    def loss(q, k, v):
        out = flash_attention(q, k, v, softmax_scale=q.shape[-1] ** -0.5)
        return jnp.sum(out.astype(F32) * do)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _attention_specs(b, s, hq, hkv, d):
    return (((b, s, hq, d), F32), ((b, s, hkv, d), BF16),
            ((b, s, hkv, d), BF16), ((b, s, hq, d), BF16))


# name = type op(...) of every instruction, and each custom call's operands
_DEF = re.compile(r"%([\w.\-]+) = (\w+)\[([\d,]*)\]")
_CALL = re.compile(r'custom-call\(([^)]*)\), custom_call_target='
                   r'"tpu_custom_call"')


def _custom_call_operand_ranks(hlo: str) -> list:
    """The rank of each operand, for every Pallas call in the program."""
    rank = {n: len(dims.split(",")) if dims else 0
            for n, _, dims in _DEF.findall(hlo)}
    return [[rank.get(o, -1) for o in re.findall(r"%([\w.\-]+)", args)]
            for args in _CALL.findall(hlo)]


# (B, S, Hq, Hkv, D) of a training step's attention, and whether the
# backward is fused there: a NeMo-12B step, and qwen3-moe-30b-a3b at 8k,
# long enough for the split backward
ATTENTION = {"nemo12b": ((2, 4096, 32, 8, 128), True),
             "qwen3_moe_8k": ((1, 8192, 32, 4, 128), False)}


@pytest.mark.parametrize("case", sorted(ATTENTION))
def test_flash_attention_compiles(case, one_chip):
    # forward and backward at the blocks the kernel picks for the shape;
    # the query arrives in fp32, as the model hands it over
    (b, s, hq, hkv, d), fused = ATTENTION[case]
    assert flash_blocks(s, d, BF16).fused == fused
    hlo = _compile(_attention_step, one_chip,
                   *_attention_specs(b, s, hq, hkv, d))
    _assert_kernel(hlo, "flash_attention")
    # none of its calls reads as a low-rank kernel to the benchmark's
    # cost model (three, four or five 2-D operands): its FLOPs are not
    # low-rank FLOPs
    calls = _custom_call_operand_ranks(hlo)
    assert len(calls) == (2 if fused else 3)  # forward, dK/dV [, dQ]
    for ranks in calls:
        assert -1 not in ranks
        assert not (len(ranks) in (3, 4, 5)
                    and all(r == 2 for r in ranks)), ranks


# the kernels no case above reaches, at small lane-dense shapes (the SSD
# kernel is not among them: Mosaic has no lowering for its cumsum)
NAMED = {
    "flash_attention": (_attention_step, _attention_specs(1, 512, 4, 1, 128)),
    "lowrank_project": (lowrank_project,
                        (((768, 1792), BF16), ((768, RANK), BF16))),
    "subspace_lion": (
        lambda b, g, m: subspace_lion(b, g, m, lr=1e-3),
        (((4096, RANK), F32),) * 3),
    "subspace_lion_q8": (
        lambda b, g, mq, ms: subspace_lion_q8(b, g, mq, ms, lr=1e-3),
        (((4096, 128), F32), ((4096, 128), F32), ((4096, 128), I8),
         ((4096, 1), F32))),
}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_every_kernel_carries_its_name(name, one_chip):
    fn, specs = NAMED[name]
    _assert_kernel(_compile(fn, one_chip, *specs), name)
