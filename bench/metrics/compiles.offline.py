"""compiles.offline: programs handed to the XLA backend inside the window
(compiled, or loaded from the persistent cache), from JAX's own
/jax/core/compile/backend_compile_duration events. Warm-up should leave
it at 0."""


def read(run):
    return len(run.compiles)
