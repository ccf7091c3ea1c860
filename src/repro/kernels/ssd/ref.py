"""Pure-jnp oracle for the SSD chunk kernel: sequential (non-chunked)
state-space recurrence — the ground-truth semantics of Mamba2's SSD layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = jnp.float32


def ssd_ref(x, dt, A, Bm, Cm, initial_state=None):
    """Token-by-token linear recurrence (exact, O(S) sequential), from
    ``initial_state`` [B,H,P,N] (None: zeros).

    x: [B,S,H,P]; dt: [B,S,H]; A: [H]; Bm, Cm: [B,S,N]
    -> (y [B,S,H,P] f32, final state [B,H,P,N] f32).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]

    def step(state, inp):
        xt, dtt, Bt, Ct = inp                    # [B,H,P],[B,H],[B,N],[B,N]
        decay = jnp.exp(dtt * A)[..., None, None]            # [B,H,1,1]
        upd = (dtt[..., None, None] * xt[..., None]
               * Bt[:, None, None, :])                       # [B,H,P,N]
        state = state * decay + upd
        y = jnp.einsum("bhpn,bn->bhp", state, Ct)
        return state, y

    state0 = jnp.zeros((Bsz, H, P, N), f32) if initial_state is None \
        else initial_state.astype(f32)
    xs = (x.transpose(1, 0, 2, 3).astype(f32),
          dt.transpose(1, 0, 2).astype(f32),
          Bm.transpose(1, 0, 2).astype(f32),
          Cm.transpose(1, 0, 2).astype(f32))
    state, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 0, 2, 3), state
