"""Time per serve flush the flush worker waits for a batch: the
``flush.gather`` spans of the traced window, over its serve flushes."""

from bench import spans


def read(ctx):
    return spans.ms_per_flush(ctx, ("flush.gather",))
