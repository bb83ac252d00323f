"""Device-idle seconds per chip of the traced solve that fall under the
program's ``download`` spans: a level's table copied from the device to
the host while no op runs (``scopes.py``)."""

import scopes


def read(ctx):
    return scopes.idle_under_span(ctx, "download")
