"""Seconds the benchmark's one jitted call takes to draw the weights in
their served form on the device (host clock, ending in a block)."""


def read(ctx):
    return ctx["init_weights_s"]
