"""Ms an iteration that the device stood idle inside the traced window
while one of the program's own host spans (`lgbm/...`) was the innermost
annotation open: the host's work the device waited for, by the program's
name. None where the trace was not read by scope."""


def read(ctx):
    red = (ctx.get("trace") or {}).get("scopes")
    if not red or not ctx.get("iters_traced"):
        return None
    return red["host_idle_s"] * 1e3 / ctx["iters_traced"]
