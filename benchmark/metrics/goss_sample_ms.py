"""Device ms an iteration under the program's scope
`lgbm/engine/goss_sample`: GOSS's two thresholds by counting select, tie
ranks and masks. None where the trace was not read by scope or no sampled
iteration was traced."""
from lib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "engine/goss_sample")
