"""Time of the fast read (span ``fabric.fast_probe``, device tail
included) per wave of the window, in microseconds."""


def read(ctx):
    sp, waves = ctx.get("spans"), ctx["counters"]["waves"]
    if not sp or "fabric.fast_probe" not in sp or not waves:
        return None
    return 1e6 * sp["fabric.fast_probe"]["incl_s"] / waves
