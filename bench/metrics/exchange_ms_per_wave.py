"""Time of the TSU exchange (span ``fabric.exchange``: the state handed
to a device pass and, across chips, the all-gather) per wave, in
milliseconds."""


def read(ctx):
    sp, waves = ctx.get("spans"), ctx["counters"]["waves"]
    if not sp or "fabric.exchange" not in sp or not waves:
        return None
    return 1e3 * sp["fabric.exchange"]["incl_s"] / waves
