"""Pack: zero padding in the solver's packed value buffers over the bytes of
the matrix's own values, ``padded_value_bytes / (nnz * value bytes)``."""


def read(ctx):
    objs = ctx["objects"]
    if len(objs) != 1:
        return None
    padded = objs[0].stats().get("padded_value_bytes")
    if padded is None:
        return None
    return padded / (ctx["nnz"] * ctx["value_bytes"])
