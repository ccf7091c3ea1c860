"""Compliant twin of violation_sharding.py — hornlint MUST stay silent."""
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

mesh = Mesh(jax.devices(), ("data", "model"))


def arity_ok(params, x, scale):
    def prog(p, a, s):
        return jnp.dot(a, p) * s

    fn = shard_map(prog, mesh=mesh,
                   in_specs=(P("model"), P(), P()),
                   out_specs=P())
    return fn(params, x, scale)


def known_axes():
    return P("data", "model")


def rank_ok():
    x = jnp.zeros((8, 16))

    def prog(a):
        return a * 2.0

    fn = shard_map(prog, mesh=mesh,
                   in_specs=(P("data", None),),
                   out_specs=P("data", None))
    return fn(x)


def bound_collective(x):
    def prog(a):
        return jax.lax.psum(a, "data")

    fn = shard_map(prog, mesh=mesh, in_specs=(P("data"),), out_specs=P())
    return fn(x)


def variable_axis(x, axis):
    # axis names from parameters are bound by the caller — not linted
    return jax.lax.psum(x, axis)


def local_mesh_axes():
    # a file-local mesh extends the axis vocabulary
    m = Mesh(jax.devices(), ("stage",))

    def prog(a):
        return jax.lax.pmean(a, "stage")

    fn = shard_map(prog, mesh=m, in_specs=(P("stage"),), out_specs=P())
    return fn(jnp.ones((4,)))
