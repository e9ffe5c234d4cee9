"""Paper Table 2: peak training-memory profile across ALL registered
gradient-estimation methods.

The paper measures GPU GB on RoBERTa-large; offline we derive the same
comparison two ways:
  1. analytic bytes (params + grads + optimizer states + activations) from
     the actual state trees — exact accounting of what each method stores;
  2. compiled ``memory_analysis()`` temp+arg bytes of the jitted train
     step for the scaled-down encoder (1-device CPU mesh).

Rows come from ``repro.methods.available()`` (one per registered paradigm
— GaLore included, so the projection-baseline column of the paper's
comparison is complete) plus the ``vanilla_lr`` ablation (full-space ZO:
``lowrank_lr`` with the low-rank classification disabled).

Expected ordering (paper): Vanilla IPA (adamw) > LowRank-IPA
(lowrank_adam) > Vanilla LR > LowRank-LR; GaLore sits between the IPA
pair — optimizer states shrink like ours, but the full gradient IS
materialised every step (its Section-2 critique, measurable here).
"""
from __future__ import annotations

import os
from typing import Dict

import jax

from repro import methods
from repro.configs import TrainConfig, get_config
from repro.models import lm

FAST = os.environ.get("REPRO_BENCH_FAST", "1") != "0"


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
               if hasattr(x, "size") and hasattr(x.dtype, "itemsize"))


def measure(cfg, tcfg, batch, seq) -> Dict[str, float]:
    """Compiled memory of one train step (bytes), registry-dispatched."""
    from repro.data.synthetic import lm_batch
    method = methods.get(tcfg.optimizer)
    params = lm.init_params(cfg, jax.random.key(0))
    data = lm_batch(0, 0, batch=batch, seq_len=seq, vocab=cfg.vocab_size)
    params, opt = method.init(params, tcfg, jax.random.key(1))
    step = method.make_inner_step(cfg, tcfg)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, data).compile()
    m = compiled.memory_analysis()
    return {
        "state_bytes": _tree_bytes(params) + _tree_bytes(opt),
        "temp_bytes": m.temp_size_in_bytes,
        "arg_bytes": m.argument_size_in_bytes,
        "total_bytes": m.temp_size_in_bytes + m.argument_size_in_bytes,
    }


def variants() -> Dict[str, TrainConfig]:
    """One row per registered method + the full-space-ZO ablation."""
    base = dict(sampler="stiefel", rank=8, lazy_k=50, min_dim_for_lowrank=64,
                total_steps=100, warmup_steps=0)
    out = {name: TrainConfig(optimizer=name, **base)
           for name in methods.available()}
    out["vanilla_lr"] = TrainConfig(optimizer="lowrank_lr",
                                    **{**base, "rank": 10**9,
                                       "min_dim_for_lowrank": 10**9})
    return out


# Serving-memory column: one ragged batch profile (slots of max_len / 1,
# 2, 4 and 8 tokens, round-robin)
SERVE_ARCHS = ("qwen2-7b", "deepseek-v2-236b", "mamba2-780m", "zamba2-7b")
SERVE_BATCH, SERVE_MAX_LEN, SERVE_PAGE = 8, 4096, 64


def serve_lengths() -> list:
    """Ragged per-slot lengths: max_len / {1, 2, 4, 8} round-robin."""
    return [SERVE_MAX_LEN // (2 ** (i % 4)) for i in range(SERVE_BATCH)]


def serving_memory() -> Dict:
    """Serving-memory column (roofline-derived): decode-cache bytes a
    ragged batch actually holds under paging vs the ``max_len``
    preallocation of ``lm.alloc_decode_state`` — one row per cache family
    (KV / MLA / SSM / hybrid).  SSM rows barely move: their state is
    length-independent by construction (that IS the family's point)."""
    from repro.analysis import roofline
    lengths = serve_lengths()
    print("arch,family,prealloc_MB,paged_MB,savings")
    out = {}
    for arch in SERVE_ARCHS:
        cfg = get_config(arch)
        pre = roofline.dense_cache_bytes(cfg, SERVE_BATCH, SERVE_MAX_LEN)
        paged = roofline.paged_cache_bytes(cfg, lengths, SERVE_PAGE)
        save = 1.0 - paged / pre if pre else 0.0
        out[arch] = {"prealloc_bytes": pre, "paged_bytes": paged,
                     "savings": save}
        print(f"{arch},{cfg.family},{pre/2**20:.1f},{paged/2**20:.1f},"
              f"{save*100:.0f}%")
    return out


def run() -> Dict:
    cfg = get_config("encoder-small").replace(
        num_layers=2 if FAST else 4)
    batch, seq = (8, 128) if FAST else (16, 256)
    print("method,family,state_MB,step_temp_MB,step_total_MB")
    out = {}
    for name, tcfg in variants().items():
        r = measure(cfg, tcfg, batch, seq)
        out[name] = r
        fam = methods.get(tcfg.optimizer).describe()["family"]
        print(f"{name},{fam},{r['state_bytes']/2**20:.2f},"
              f"{r['temp_bytes']/2**20:.2f},{r['total_bytes']/2**20:.2f}")
    # every registered low-rank paradigm (present and future — rows come
    # from the registry, so a newly registered lowrank_* method lands
    # here with zero edits) must beat the dense-Adam memory baseline
    lowrank = [n for n in methods.available() if n.startswith("lowrank_")]
    ok = all(out[n]["total_bytes"] < out["adamw"]["total_bytes"]
             for n in lowrank)
    print(f"# lowrank ({', '.join(lowrank)}) beats full-BP memory: "
          f"{'OK' if ok else 'VIOLATED'}")
    out["serving"] = serving_memory()
    return out


def main():
    run()


if __name__ == "__main__":
    main()
