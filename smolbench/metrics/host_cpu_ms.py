"""Host entropy decode and staging: thread CPU time per item, ms, beside the
wall time that ``host_decode_ms`` reads (``stats()``'s ``host_cpu_seconds``
over ``host_items`` in the window).  None where the runtime keeps no such
counter."""


def read(ctx):
    s0, s1 = (ctx[k]["stats"].scheduler.stats for k in ("s0", "s1"))
    items = s1.host_items - s0.host_items
    if items <= 0 or not hasattr(s1, "host_cpu_seconds"):
        return None
    return (s1.host_cpu_seconds - s0.host_cpu_seconds) / items * 1e3
