"""Backend compiles in the window (reads of the persistent compile cache
included), counted from ``jax.monitoring`` by the harness."""


def read(ctx):
    return ctx["compiles"]
