"""Host time per step to make the batch, place it on the chips and fetch the
loss: the benchmark's own ``bench.batch``, ``bench.place`` and ``bench.fetch``
spans, which the loop runs while the device waits."""

SPANS = ("bench.batch", "bench.place", "bench.fetch")


def read(ctx):
    total = sum(end - start for name, start, end in ctx.host if name in SPANS)
    return total / ctx.steps / 1e6 if total else None
