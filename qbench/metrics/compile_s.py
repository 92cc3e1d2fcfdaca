"""Host seconds of lower() + compile() of the cell's program in set-up; a
persistent-cache load once the cell has run in this checkout."""


def read(ctx):
    return ctx.get("compile_s")
