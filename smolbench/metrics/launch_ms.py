"""Dispatch: the launch part of the blocking device call (placement, H2D
copy-in, enqueue), mean ms per batch over the window (``stats()``'s
``launch_seconds``, the ``smol.launch`` spans).  None where the runtime keeps
no such counter."""


def read(ctx):
    s0, s1 = (ctx[k]["stats"].scheduler.stats for k in ("s0", "s1"))
    batches = s1.batches - s0.batches
    if batches <= 0 or not hasattr(s1, "launch_seconds"):
        return None
    return (s1.launch_seconds - s0.launch_seconds) / batches * 1e3
