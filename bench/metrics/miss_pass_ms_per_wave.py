"""Time of the miss pass (span ``fabric.miss_pass``: its dispatch and
its deferred decode) per wave that had misses, in milliseconds."""


def read(ctx):
    sp, waves = ctx.get("spans"), ctx["counters"]["waves_with_misses"]
    if not sp or "fabric.miss_pass" not in sp or not waves:
        return None
    return 1e3 * sp["fabric.miss_pass"]["incl_s"] / waves
