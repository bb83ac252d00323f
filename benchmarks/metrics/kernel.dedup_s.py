"""Device seconds per chip of the traced solve's ops under the program's
``dedup`` scope (docs/OBSERVABILITY.md "Device scopes"): the "XLA Ops"
events inside the ``benchmark.window`` span, each by the innermost scope
on its name path (``scopes.py``)."""

import scopes


def read(ctx):
    return scopes.kernel_seconds(ctx, "dedup")
