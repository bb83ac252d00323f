"""Device seconds per chip of the traced solve's ops under the program's
``route`` scope (docs/OBSERVABILITY.md "Device scopes"): the sharded
engine's owner bucketing and its exchanges over the mesh, the "XLA Ops"
events inside the ``benchmark.window`` span, each by the innermost scope
on its name path (``scopes.py``)."""

import scopes


def read(ctx):
    return scopes.kernel_seconds(ctx, "route")
