"""Backend-switched SSD chunk scan over a stack of per-slot states."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.backend import get_backend
from repro.kernels.ssd.kernel import ssd_chunk_scan as _pallas


def ssd_chunk_scan(x, dt, A, Bm, Cm, states, fresh, layer, *,
                   chunk: int = 256, backend=None):
    """(y [B,S,H,P], ``states`` with row ``layer`` advanced): each
    sequence b continues from ``states[layer, b]`` [H,P,N] f32, or from
    zeros where ``fresh[b]``.  The "ref" backend runs the model's jnp
    chunked form (``models.ssm.ssd_chunked``); the others the Pallas
    kernel, which updates the row in place."""
    backend = backend or get_backend()
    if backend == "ref":
        from repro.models.ssm import ssd_chunked
        state = jax.lax.dynamic_index_in_dim(states, layer, 0,
                                             keepdims=False)
        state = jnp.where(fresh[:, None, None, None], 0.0, state)
        y, state = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk,
                               initial_state=state)
        return y, jax.lax.dynamic_update_index_in_dim(states, state, layer,
                                                      0)
    return _pallas(x, dt, A, Bm, Cm, states, jnp.where(fresh, 0.0, 1.0),
                   jnp.reshape(layer, (1,)).astype(jnp.int32), chunk=chunk,
                   interpret=backend == "interpret")
