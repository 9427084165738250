"""Mean of a histogram's samples of the window: its ``.sum`` over its
``.count``, times ``scale``.  Nothing where it took no sample."""


def read(ctx, name, scale=1.0):
    c = ctx["counters"]
    count = c.get(f"{name}.count", 0.0)
    if count <= 0 or f"{name}.sum" not in c:
        return None
    return scale * c[f"{name}.sum"] / count
