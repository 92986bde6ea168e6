"""Set-up: process start to the first timed operation -- jax and TPU
start, inputs from the seed, the warm-up operation, programs from the
compile cache."""


def read(ctx):
    return ctx.setup_s
