"""Host build: seconds from the matrix on the host to a built solver
(analysis, planning, packing), on the host clock."""


def read(ctx):
    return ctx.get("build_s")
