"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling)
and a pure-jnp oracle in ref.py; dispatch.py is the one entry layer (pad
to tile, routing, rank packing, kernel cache).  Validated via interpret
mode on CPU; targeted at TPU v5e (MXU 128x128, ~16 MB VMEM).
"""
from . import dispatch, ref  # noqa: F401
