"""Host clock around Dataset.construct() to block_until_ready (set-up)."""


def read(ctx):
    return ctx["spans"].get("ingest")
