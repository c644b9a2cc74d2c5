"""Host meshes for the serve and train drivers."""
from __future__ import annotations

import jax


def make_host_mesh(model: int = 1, seq: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over however many (real) devices exist — smoke tests.

    ``seq`` > 1 inserts a ``seq`` axis between data and model (capped at
    what the device count allows), for exercising the long-context KV
    layout on host devices.
    """
    n = jax.device_count()
    model = min(model, n)
    seq = max(1, min(seq, n // model))
    while (n // model) % seq:
        seq -= 1                      # largest feasible seq axis <= requested
    if seq > 1:
        return jax.make_mesh(
            (n // (model * seq), seq, model), ("data", "seq", "model"))
    return jax.make_mesh((n // model, model), ("data", "model"))
