"""Pallas TPU kernels for the model zoo's compute hot spots.

Each kernel ships three artifacts (per the repo convention):
``<name>.py`` (pl.pallas_call + BlockSpec), ``ops.py`` (jit wrapper),
``ref.py`` (pure-jnp oracle used by the allclose sweeps in tests/).
``splash.py`` instead wraps JAX's installed splash-attention kernels for
``models.layers.attention``, whose jnp path is its oracle.
"""

from .ops import flash_attention, rmsnorm, ssd_scan
from . import ref

__all__ = ["flash_attention", "rmsnorm", "ssd_scan", "ref"]
