"""Pallas TPU kernels (``lease_validate``, ``flash_attention``,
``ssd_scan``), their jnp oracles (``ref``) and the dispatch point (``ops``).

The kernels take ``interpret`` as given; :func:`kernel_mode` is the one
place that looks at the backend.
"""
from __future__ import annotations

from typing import Optional


def kernel_mode(backend: str) -> Optional[bool]:
    """Where a kernel choice (``"auto"``, ``"pallas"``, anything else for
    the jnp path) lands on this process's backend: ``None`` for the jnp
    path, else the kernel's ``interpret`` flag.  ``"auto"`` compiles the
    kernel on TPU and takes the jnp path elsewhere; ``"pallas"`` compiles
    it on TPU and interprets it elsewhere."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if backend == "auto":
        return False if on_tpu else None
    if backend == "pallas":
        return not on_tpu
    return None
