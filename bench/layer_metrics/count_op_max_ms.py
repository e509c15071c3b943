"""The longest call into the count op in the window, in ms: the longest
``kernel.count`` span (the kernel's dispatch, its wait for the device and
the launch record).  A flush's launch over the 4M-row base takes about
45 ms, a bulk job's single 16,384-target launch about 2.1 s; the program's
freezes of the whole process inside this call add 0.7-2 s."""


def read(ctx):
    spans = [s.t1 - s.t0 for s in ctx.get("spans") or []
             if s.name == "kernel.count"]
    return None if not spans else max(spans) * 1e3
