"""Host time of the HTTP front per read: the self time of each
``http.request`` span (all of ``ServiceApp.handle`` for a read) less its
``http.wait`` (the wait for the flush that answers it), averaged over
the reads that start in the traced window."""

from bench import spans


def read(ctx):
    return spans.self_ms(ctx, "http.request", "http.wait")
