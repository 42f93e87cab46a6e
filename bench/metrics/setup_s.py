"""Set-up: process start to the first scheduled request (device start,
weights made on the device, engine and pool, compile-cache loads and the
warm-up of every reachable shape)."""


def read(ctx):
    return ctx.setup_s
