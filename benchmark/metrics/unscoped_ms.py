"""Device ms an iteration of leaf ops that carry no `lgbm/` scope: the
grower's bookkeeping no scope wraps and ops XLA made without metadata.
None where the trace was not read by scope."""
from lib import scopes


def read(ctx):
    red = (ctx.get("trace") or {}).get("scopes")
    if not red or not ctx.get("iters_traced"):
        return None
    return (red["scopes"].get(scopes.UNSCOPED, 0.0) * 1e3
            / ctx["iters_traced"])
