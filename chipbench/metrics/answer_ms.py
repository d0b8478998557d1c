"""Host time per serve flush spent turning device results into answers:
the ``serve.hits`` (threshold hits from the score matrix) and
``flush.answer`` (result slicing, release of the waiters, per-request
bookkeeping) spans of the traced window, over its serve flushes."""

from bench import spans


def read(ctx):
    return spans.ms_per_flush(ctx, ("serve.hits", "flush.answer"))
