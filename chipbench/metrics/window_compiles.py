"""Cold start: compiles counted inside the window; expected 0."""


def read(ctx):
    w = ctx["window"]
    return w["compile_at_end"]["compiles"] - w["compile_at_start"]["compiles"]
