"""Peak bytes in use on the fullest chip over its limit, after the window
and the hold-out scoring (a process's peak never falls, so set-up's device
ingest may have set it)."""


def read(ctx):
    if not ctx.get("memory_limit_bytes"):
        return None
    return 100.0 * ctx["memory_peak_bytes"] / ctx["memory_limit_bytes"]
