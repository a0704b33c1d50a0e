"""Share of the window in which the epoch loop waits for the next epoch's
staged plan (``EpochPrefetcher.get``), by the host clock."""

from readers import share


def read(ctx):
    return share(ctx["plan_wait_s"], ctx["window_s"])
