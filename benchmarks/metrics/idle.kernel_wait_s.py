"""Device-idle seconds per chip of the traced solve that fall under the
program's ``kernel_wait`` spans: the solve thread blocked on a background
compile of the kernel it needs (``scopes.py``)."""

import scopes


def read(ctx):
    return scopes.idle_under_span(ctx, "kernel_wait")
