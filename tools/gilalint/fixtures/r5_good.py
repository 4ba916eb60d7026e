"""R5 negative: one spec per parameter, declared axes only."""
from jax.sharding import PartitionSpec as P

from jax import shard_map


def local(pos, w, params):
    return pos


def make(mesh):
    return shard_map(local, mesh=mesh,
                     in_specs=(P("data", None), P("data"), P()),
                     out_specs=P("data", None))
