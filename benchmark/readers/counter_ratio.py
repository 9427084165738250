"""Sum of some counters' growth over the window, over the sum of
others', times ``scale``.  Nothing where the denominator did not move
or a counter is not there."""


def read(ctx, num, den, scale=1.0):
    c = ctx["counters"]
    if any(n not in c for n in list(num) + list(den)):
        return None
    bottom = sum(c[n] for n in den)
    if bottom <= 0:
        return None
    return scale * sum(c[n] for n in num) / bottom
