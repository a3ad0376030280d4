"""Cold start: seconds spent compiling before the window started
(``compilation.compile_metrics()``, a program counter)."""


def read(ctx):
    return ctx["window"]["compile_at_start"]["compile_seconds"]
