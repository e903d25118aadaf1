"""Share of its roofline reached by the fast read in the traced window:
the least time the chip needs for the reads' logical work (requests x
bytes per request, ``bench/lib/peaks.py``) at the HBM peak, over the
device time of every run of the fast read's program (``jit_fast``), the
copies that stage its probe kernel's operands included, in percent."""
from bench.lib import peaks

PROGRAM = "jit_fast"


def read(ctx):
    tr, work = ctx.get("trace"), ctx.get("work", {}).get("fast_read")
    if not tr or not work:
        return None
    need = sum(n * peaks.fast_read_bytes(*shape) for n, shape in work)
    return peaks.roofline_share(need, tr["programs"].get(PROGRAM, 0.0),
                                ctx["device_kind"])
