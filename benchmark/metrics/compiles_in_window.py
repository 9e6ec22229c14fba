"""Backend compiles JAX reported inside the measured window (0 when
set-up warmed every shape the window uses)."""


def read(ctx):
    return float(ctx.compiles_in_window)
