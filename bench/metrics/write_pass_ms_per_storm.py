"""Time of the batched write pass (span ``fabric.write_pass``) per
publish storm of the window, in milliseconds."""


def read(ctx):
    sp, storms = ctx.get("spans"), ctx["counters"]["storms"]
    if not sp or "fabric.write_pass" not in sp or not storms:
        return None
    return 1e3 * sp["fabric.write_pass"]["incl_s"] / storms
