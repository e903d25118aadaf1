"""Self time of the fabric's host spans ``fabric.pack``,
``fabric.decode`` and ``fabric.donate`` (their ``.device`` children
excluded), per read served in the window, in microseconds."""

SPANS = ("fabric.pack", "fabric.decode", "fabric.donate")


def read(ctx):
    sp, n = ctx.get("spans"), ctx["counters"]["reads"]
    if not sp or not n:
        return None
    return 1e6 * sum(sp[s]["self_s"] for s in SPANS if s in sp) / n
