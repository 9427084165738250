"""``counter_ratio`` with something taken off either sum first:
``(sum(num) - sum(num_less)) / (sum(den) - sum(den_less))``, times
``scale``.  For a window whose counters hold a known piece of work that
is not the window's, kept by whoever ran it under a name of its own
(``generators/closed_loop_scrub``: the quiet pass after the close).
Nothing where a counter is not there or the denominator is not above
zero."""


def read(ctx, num, den, num_less=(), den_less=(), scale=1.0):
    c = ctx["counters"]
    names = list(num) + list(den) + list(num_less) + list(den_less)
    if any(n not in c for n in names):
        return None
    bottom = sum(c[n] for n in den) - sum(c[n] for n in den_less)
    if bottom <= 0:
        return None
    top = sum(c[n] for n in num) - sum(c[n] for n in num_less)
    return scale * top / bottom
