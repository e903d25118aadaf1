"""The scheduler's own Python per read served in the window, in
microseconds: the self time of the ``sched.*`` spans of
``runtime/scheduler.replay`` (the fabric's spans under them take their
own time), over the window's reads.

``sched.dispatch`` and ``sched.storm`` are left out.  Each wraps a call
into the backend, and its self time is that call's entry outside the
fabric's spans: the harness's recording proxy and, once in a traced run,
the proxy's ``stop_trace()``, which takes seconds.  What the scheduler
itself does in them is a few list appends per wave, and a storm's keys
and values once per storm (the hit cell has no storms); the wave's key
strings are ``sched.keys``, which counts.

Read, like the device metrics, only in a run whose profiler trace holds
a device: a run on the CPU rehearses the harness and gives no time."""

LEFT_OUT = ("sched.dispatch", "sched.storm")


def read(ctx):
    tr, sp, n = ctx.get("trace"), ctx.get("spans"), ctx["counters"].get(
        "reads")
    if not tr or not tr["n_devices"]:
        return None
    own = [v["self_s"] for name, v in (sp or {}).items()
           if name.startswith("sched.") and name not in LEFT_OUT]
    if not own or not n:
        return None
    return 1e6 * sum(own) / n
