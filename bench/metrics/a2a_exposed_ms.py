"""All-to-all time per step during which no other op runs, in ms, on the
chip where it is longest.  Nothing where the step runs no all-to-all."""


def read(ctx):
    s = ctx["summary"]
    if s.scope_s("a2a") <= 0:
        return None
    return max(c.a2a_exposed_s for c in s.chips) / ctx["steps"] * 1e3
