"""Model FLOP utilisation of the training step: FLOPs of forward and
backward per trained edge (``work.step_flops``, no recomputation) times
the window's trained edges per second, over chips times the bf16 peak."""

import work
from readers import model, share


def read(ctx):
    if "peaks" not in ctx:
        return None
    m = model(ctx)
    per_edge = work.step_flops(m) / m["batch_size"]
    rate = ctx["edges"] / ctx["window_s"]
    return share(per_edge * rate, ctx["chips"] * ctx["peaks"]["bf16_flops"])
