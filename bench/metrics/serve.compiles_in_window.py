"""serve.compiles_in_window: executables JAX compiled or loaded from
its cache while the window was open (``jax.monitoring``); each is a
shape that set-up did not warm."""


def read(ctx):
    return len(ctx["compiles"])
