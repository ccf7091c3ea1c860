"""Kernel backend switch.

  * "pallas"    — compiled pallas_call, TPU target (production).  The only
                  backend a process with a TPU runs.
  * "interpret" — pallas_call(interpret=True): the kernel body executes in
                  Python on CPU; used by correctness tests.
  * "ref"       — pure-jnp oracle (ref.py); the CPU default (Pallas cannot
                  lower to the CPU backend) and the allclose reference.
"""
from __future__ import annotations

import jax

_BACKEND = None


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def default_backend() -> str:
    return "pallas" if _on_tpu() else "ref"


def get_backend() -> str:
    global _BACKEND
    if _BACKEND is None:
        _BACKEND = default_backend()
    return _BACKEND


def with_reference_grad(kernel, reference):
    """``kernel``'s outputs with ``reference``'s gradients.  The Pallas
    kernels here are forward-only; a training step differentiates through
    the pure-jnp oracle they are tested against."""
    @jax.custom_vjp
    def fn(*args):
        return kernel(*args)

    def fwd(*args):
        return kernel(*args), args

    def bwd(args, g):
        return jax.vjp(reference, *args)[1](g)

    fn.defvjp(fwd, bwd)
    return fn


def set_backend(name: str) -> None:
    """Select a backend on CPU (tests switch to "interpret"); a process
    with a TPU runs the compiled kernels only."""
    if name not in ("pallas", "interpret", "ref"):
        raise ValueError(f"unknown kernel backend {name!r}")
    if name != "pallas" and _on_tpu():
        raise ValueError(f"kernel backend {name!r} requested on a TPU")
    global _BACKEND
    _BACKEND = name
