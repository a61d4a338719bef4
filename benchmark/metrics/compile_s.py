"""Backend-compile seconds over set-up, by the benchmark's own
jax.monitoring listener (about 0 from a warm cache)."""


def read(ctx):
    return ctx.get("compile_s")
