"""R5 positives: shard_map arity mismatch + undeclared mesh axis."""
from jax.sharding import PartitionSpec as P

from jax import shard_map


def local(pos, w, params):
    return pos


def make(mesh):
    return shard_map(local, mesh=mesh,
                     in_specs=(P("data"), P("rows")),   # 2 specs, 3 params;
                     out_specs=P("data"))               # 'rows' undeclared
