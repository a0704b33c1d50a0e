"""Seconds from the start of set-up (data, weights, staging, compile or
cache load) to the end of the first epoch, which warms up every shape."""


def read(ctx):
    return ctx["setup_s"]
