"""Backend compile seconds of the run's set-up, as JAX reports them
(``/jax/core/compile/backend_compile_duration`` through ``jax.monitoring``);
a program read from the persistent cache counts its retrieval."""


def read(ctx):
    c = ctx.get("compile")
    if c is None:
        return None
    return c["seconds"]
