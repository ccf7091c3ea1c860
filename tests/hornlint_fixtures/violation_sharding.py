"""Seeded HL5xx violations — hornlint MUST exit nonzero on this file."""
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

mesh = Mesh(jax.devices(), ("data", "model"))


def arity_mismatch(params, x):                        # HL501
    def prog(p, a, scale):
        return jnp.dot(a, p) * scale

    fn = shard_map(prog, mesh=mesh,
                   in_specs=(P("model"), P()),        # 2 specs, 3 params
                   out_specs=P())
    return fn(params, x)


def bogus_axis():                                     # HL502
    return P("data", "modle")                         # typo'd axis name


def rank_overflow():                                  # HL503
    x = jnp.zeros((8, 16))

    def prog(a):
        return a * 2.0

    fn = shard_map(prog, mesh=mesh,
                   in_specs=(P("data", "model", None),),   # 3 entries, rank 2
                   out_specs=P("data", "model", None))
    return fn(x)


def unbound_collective(x):                            # HL504: no shard_map
    return jax.lax.psum(x, "data")


def unknown_collective_axis(x):                       # HL504: bad axis name
    def prog(a):
        return jax.lax.psum(a, "stage9")

    fn = shard_map(prog, mesh=mesh, in_specs=(P("data"),), out_specs=P())
    return fn(x)
