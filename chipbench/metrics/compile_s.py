"""Seconds JAX spent compiling in set-up (tracing, lowering, and compiling
or loading each program from the persistent cache), from JAX's own compile
spans.  Moves ``setup_s``."""
UNIT = "s"


def read(ctx):
    return ctx.compile_s if ctx.compile_s > 0 else None
