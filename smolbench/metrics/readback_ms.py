"""Dispatch: the readback part of the blocking device call (device completion,
D2H, the interpreter back), mean ms per batch over the window (``stats()``'s
``readback_seconds``, the ``smol.readback`` spans).  None where the runtime
keeps no such counter."""


def read(ctx):
    s0, s1 = (ctx[k]["stats"].scheduler.stats for k in ("s0", "s1"))
    batches = s1.batches - s0.batches
    if batches <= 0 or not hasattr(s1, "readback_seconds"):
        return None
    return (s1.readback_seconds - s0.readback_seconds) / batches * 1e3
