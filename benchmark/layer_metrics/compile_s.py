"""Backend compile seconds of the run (jax monitoring events; 0 only when
every program came from the persistent cache and took under the event's
resolution). Source: program_counter."""


def read(ctx):
    return ctx["facts"]["compile"]["compile_s"]
