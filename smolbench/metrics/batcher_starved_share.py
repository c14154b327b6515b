"""Batcher: share of the window each replica batcher spent blocked on the
ready queue with nothing staged, % (``stats()``'s ``starved_seconds``, the
``smol.starved`` spans, over the window and the replica count).  None where
the runtime keeps no such counter."""


def read(ctx):
    s0, s1 = (ctx[k]["stats"] for k in ("s0", "s1"))
    if not hasattr(s1.scheduler.stats, "starved_seconds"):
        return None
    starved = s1.scheduler.stats.starved_seconds - s0.scheduler.stats.starved_seconds
    return 100.0 * starved / (ctx["window_s"] * len(s1.mesh.replicas))
