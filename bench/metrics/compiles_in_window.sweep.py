"""Chunk programs compiled inside the window (``sweep.compile`` spans);
0 when set-up warmed every shape the grids use."""


def read(ctx):
    from bench.lib.readers import count

    return count(ctx, "sweep.compile")
