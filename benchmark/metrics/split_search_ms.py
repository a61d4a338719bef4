"""Device ms an iteration under the program's scopes
`lgbm/grower/split_search` and, inside it, `lgbm/grower/cat_search`: the
threshold scan and the categorical candidates. None where the trace was not
read by scope."""
from lib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "grower/split_search", "grower/cat_search")
