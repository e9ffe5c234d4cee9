"""Training traffic: token batches made from the run's seed.

A copy of the program's synthetic LM source (``repro.data.synthetic.
lm_batch``), kept here so that the yardstick cannot change with the
program: a random walk over ``n_modes`` modes picks, per position, which
slice of the vocabulary the token is drawn from.  Every batch is a pure
function of (seed, step), so every row of every step differs and two runs
of one seed see the same tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (also past 32 signed bits)."""
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("batch", "seq_len", "vocab",
                                              "n_modes"))
def lm_batch(key, step, *, batch: int, seq_len: int, vocab: int,
             n_modes: int = 8) -> dict:
    """Tokens and next-token labels for one step."""
    key = jax.random.fold_in(key, step)
    kmode, ktok, kwalk = jax.random.split(key, 3)
    mode0 = jax.random.randint(kmode, (batch, 1), 0, n_modes)
    walk = jax.random.uniform(kwalk, (batch, seq_len + 1)) < 0.05
    mode = (mode0 + jnp.cumsum(walk, axis=1)) % n_modes
    width = max(vocab // n_modes, 2)
    offs = jax.random.randint(ktok, (batch, seq_len + 1), 0, width)
    toks = (mode * width + offs).astype(jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


class Loader:
    """The Trainer's loader: ``loader(step) -> batch`` on the device."""

    def __init__(self, seed: int, traffic: dict, vocab: int):
        self.key = jax.random.fold_in(seed_key(seed), 0xDA7A)
        self.kw = dict(batch=int(traffic["batch"]),
                       seq_len=int(traffic["seq"]), vocab=vocab,
                       n_modes=int(traffic.get("n_modes", 8)))

    def __call__(self, step) -> dict:
        with jax.profiler.TraceAnnotation("bench.loader"):
            return lm_batch(self.key, step, **self.kw)
