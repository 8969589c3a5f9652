"""Seconds from the start of the process to the first timed round: data,
weights, the simulation, compilation and the warm-up round."""


def read(ctx):
    return ctx["setup_s"]
