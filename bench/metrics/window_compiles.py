"""Model steps: executables compiled or loaded from the compile cache
while the traffic ran (a ``jax.monitoring`` listener).  Set-up should
have warmed every one, so this reads 0."""


def read(ctx):
    return len(ctx.compiles)
