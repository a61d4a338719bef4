"""Device ms an iteration under the program's scope `lgbm/grower/move`:
the leaf-ordered partition's move (the plan's prefix sums, the tables, the
`partition_move` kernel passes and their blend). None where the program has
no such scope (before it, the move was inside `lgbm/grower/partition`)."""
from lib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "grower/move")
