"""Peak allocator bytes on the fullest device after the window, in GB."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9
