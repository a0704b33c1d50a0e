"""Stream edges trained in the window over the window's whole time, epoch
boundaries included."""


def read(ctx):
    return ctx["edges"] / ctx["window_s"]
