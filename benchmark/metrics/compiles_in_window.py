"""Programs compiled or fetched inside the window; has to read 0."""


def read(ctx):
    return ctx.get("compiles_in_window")
