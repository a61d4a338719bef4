"""Device ms an iteration under the program's scope
`lgbm/engine/score_update`: the leaf-value select and the add into the
training scores. None where the trace was not read by scope."""
from lib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "engine/score_update")
