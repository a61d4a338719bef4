"""Device ms an iteration under the program's scope `lgbm/grower/partition`:
the grower's row -> leaf passes (the table's or the compact buffer's in the
loop, the leaf-ordered partition's per-position pass) and `route_rows` once
after the loop. None where the trace was not read by scope."""
from lib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "grower/partition")
