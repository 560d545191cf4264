"""Mean milliseconds per step over the window (host clock, whole window)."""


def read(ctx):
    m = ctx["measured"]
    if not m.get("steps"):
        return None
    return 1e3 * m["seconds"] / m["steps"]
