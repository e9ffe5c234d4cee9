"""Sharded outer-resample tests.

Two layers:

* In-process: the per-row key-split contract of every batched sampler —
  ``batched(key, batch)[g] == single(split(key, batch)[g])`` bit-exactly.
  This is the property that makes the G-sharded draw equal the replicated
  reference: each shard regenerates exactly its rows' draws.
* Subprocess (8 host devices — XLA device count must be set before any
  jax import, so these follow tests/test_dryrun.py's pattern): the same
  draw executed with a G-sharded output/energy on a real mesh matches
  the unsharded reference bit-for-bit, and a G-sharded checkpoint
  save -> restore round-trips.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import samplers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("name", samplers.available_batched())
def test_batched_draw_matches_per_key_single(name):
    key = jax.random.key(7)
    batch, n, r = 5, 96, 8
    kw = {}
    if name == "dependent_diag":
        kw["diag_energy"] = jax.random.uniform(jax.random.key(3), (batch, n))
    vb = samplers.sample_v_batched(name, key, batch, n, r,
                                   dtype=jnp.float32, **kw)
    assert vb.shape == (batch, n, r)
    keys = jax.random.split(key, batch)
    for g in range(batch):
        skw = {}
        if name == "dependent_diag":
            skw["diag_energy"] = kw["diag_energy"][g]
        vs = samplers.sample_v(name, keys[g], n, r, dtype=jnp.float32, **skw)
        np.testing.assert_array_equal(np.asarray(vb[g]), np.asarray(vs),
                                      err_msg=f"{name} row {g}")


def _run_sub(script: str, timeout: int = 420) -> None:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK" in r.stdout


_SHARDED_DRAW = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import samplers

mesh = Mesh(np.array(jax.devices()).reshape(8), ("g",))
key = jax.random.key(11)
batch, n, r = 8, 64, 8
for name in samplers.available_batched():
    kw, ref_kw = {}, {}
    if name == "dependent_diag":
        e = jax.random.uniform(jax.random.key(5), (batch, n))
        ref_kw["diag_energy"] = e
        kw["diag_energy"] = jax.device_put(
            e, NamedSharding(mesh, P("g", None)))
    def draw(k, **kws):
        return samplers.sample_v_batched(name, k, batch, n, r, **kws)

    # reference: the same jitted program, replicated on one device (an
    # eager reference can differ by 1 ulp of XLA constant folding)
    ref = jax.jit(draw)(key, **ref_kw)
    out = jax.jit(draw, out_shardings=NamedSharding(
        mesh, P("g", None, None)))(key, **kw)
    assert out.sharding.spec == P("g", None, None), (name, out.sharding)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    print(name, "sharded == replicated")
print("OK")
"""


def test_g_sharded_draw_equals_replicated_subprocess():
    """Every batched sampler, drawn with its output (and energy) G-sharded
    over an 8-device mesh, is bit-identical to the replicated draw."""
    _run_sub(_SHARDED_DRAW)


_SHARDED_CKPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro import configs, methods
from repro.models import lm
from repro.sharding import rules
from repro.train import checkpoint

cfg = configs.get_config("llama-tiny")
tcfg = configs.TrainConfig()
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
specs = lm.param_specs(cfg)
method = methods.get("lowrank_adam")
p, s = method.init(lm.init_params(cfg, jax.random.key(0)), tcfg,
                   jax.random.key(1))
p_ps, o_ps = method.pspecs(mesh, specs, p, s)
p_sh = rules.named_shardings(mesh, p_ps)
o_sh = rules.named_shardings(mesh, o_ps)

def put(tree, sh):
    return jax.tree.map(
        lambda x, ns: x if jax.dtypes.issubdtype(
            getattr(x, "dtype", np.float32), jax.dtypes.prng_key)
        else jax.device_put(x, ns), tree, sh)

p_sharded, s_sharded = put(p, p_sh), put(s, o_sh)
wd = tempfile.mkdtemp()
checkpoint.save(wd, 3, {"params": p_sharded, "opt": s_sharded})
got, _manifest = checkpoint.restore(wd, 3, {"params": p, "opt": s},
                                    shardings={"params": p_sh, "opt": o_sh})
for a, b in zip(jax.tree.leaves({"params": p, "opt": s}),
                jax.tree.leaves(got)):
    if jax.dtypes.issubdtype(getattr(a, "dtype", np.float32),
                             jax.dtypes.prng_key):
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(a)),
            np.asarray(jax.random.key_data(b)))
    else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
# restored grouped leaves actually landed sharded
flat_sh = jax.tree.leaves(
    o_sh, is_leaf=lambda x: hasattr(x, "spec"))
flat_got = jax.tree.leaves(got["opt"])
assert any(len(x.sharding.device_set) > 1 for x in flat_got
           if hasattr(x, "sharding")), "nothing restored sharded"
print("OK")
"""


def test_sharded_checkpoint_roundtrip_subprocess():
    """G-sharded grouped params + state save -> restore bit-identically,
    with restore(shardings=...) landing leaves back on the mesh."""
    _run_sub(_SHARDED_CKPT)


_TRAINER_SHARD = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
import jax, numpy as np
from repro import configs
from repro.data.synthetic import StatelessLoader
from repro.sharding import ctx
from repro.train.trainer import Trainer

cfg = configs.get_config("llama-tiny")
tcfg = configs.TrainConfig(rank=8, lazy_k=2, warmup_steps=1, total_steps=3)

def run(mesh=None):
    loader = StatelessLoader("lm", seed=0, batch=4, seq_len=16,
                             vocab=cfg.vocab_size)
    tr = Trainer(cfg, tcfg, loader)
    split = []
    if mesh is not None:
        tr.shard(mesh)
        flat = jax.tree_util.tree_flatten_with_path(tr.opt_state)[0]
        split = [(p, a.sharding.spec) for p, a in flat
                 if "groups" in jax.tree_util.keystr(p)
                 and a.sharding.spec and a.sharding.spec[0] is not None]
        assert split, "no grouped buffer is G-split"
    rep = tr.run(1)            # compiles the inner step
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dt, **kw: compiles.append(ev)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    rep.losses += tr.run(2).losses     # inner, outer + inner
    return tr, rep, split, len(compiles)

_, one, _, _ = run()
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx.set_mesh(mesh)
tr, four, split, compiles = run(mesh)
ctx.set_mesh(None)
np.testing.assert_allclose(four.losses, one.losses, rtol=1e-4)
# the carried state keeps its placement: after the first step only the
# outer step compiles, no inner step sees new input shardings
assert compiles == 1, compiles
after = dict(jax.tree_util.tree_flatten_with_path(tr.opt_state)[0])
for path, spec in split:
    a = after[path]
    # the outer resample's fresh V keeps its placement: G-sliced over
    # the four devices, not replicated
    assert a.sharding.spec == spec, (path, a.sharding.spec, spec)
    assert len({s.device for s in a.addressable_shards}) == 4
    assert all(s.data.shape[0] < a.shape[0] for s in a.addressable_shards)
print("OK", len(split))
"""


def test_trainer_shard_keeps_g_split_subprocess():
    """``Trainer.shard`` places the grouped state on a (data=2, model=2)
    mesh of host devices; the losses match the unsharded run, no step
    after the first recompiles, and every G-split buffer, the resampled V
    included, keeps its split after the outer step."""
    _run_sub(_TRAINER_SHARD)
