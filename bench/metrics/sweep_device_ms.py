"""Device busy time per engine sweep in the traced window (the union of
the device's operation intervals over the sweeps traced), in
milliseconds."""


def read(ctx):
    tr, n = ctx.get("trace"), ctx["counters"].get("sweeps_traced")
    if not tr or not n or tr["busy_s"] <= 0:
        return None
    return 1e3 * tr["busy_s"] / n
