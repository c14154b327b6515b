"""Batcher: batch formation (co-member waits within the batching window and
staging copies), mean ms per batch over the window (``stats()``'s
``batch_form_seconds``, the ``smol.batch_form`` spans).  None where the
runtime keeps no such counter."""


def read(ctx):
    s0, s1 = (ctx[k]["stats"].scheduler.stats for k in ("s0", "s1"))
    batches = s1.batches - s0.batches
    if batches <= 0 or not hasattr(s1, "batch_form_seconds"):
        return None
    return (s1.batch_form_seconds - s0.batch_form_seconds) / batches * 1e3
