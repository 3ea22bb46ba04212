"""A count the driver read at the window's edges, or the ratio of two, as
a number or a percentage."""


def read(ctx, counter, over=None, percent=False):
    c = ctx["run"]["counters"]
    if counter not in c or (over is not None and not c.get(over)):
        return None
    v = c[counter] / c[over] if over is not None else c[counter]
    return 100.0 * v if percent else float(v)
