"""Device memory of the fullest device, in MB (10^6 bytes): the larger of
the runtime's peak bytes in use after the window and the compiled epoch
program's own bytes (arguments + outputs - aliased + temporaries, by
``memory_analysis()``), since the runtime's peak leaves the program's
temporaries out."""


def read(ctx):
    seen = [b for b in (ctx["memory_peak_bytes"], ctx.get("program_bytes"))
            if b is not None]
    return max(seen) / 1e6 if seen else None
