"""Share of the device op time that the scope join puts down to no
``repro.*`` scope (a guard, not a rate: it rises when device work leaves
the scoped layers, or loses its ``op_name`` metadata)."""

from chipbench import scopes


def read(ctx):
    share = scopes.unscoped_share(ctx)
    return None if share is None else 100.0 * share
