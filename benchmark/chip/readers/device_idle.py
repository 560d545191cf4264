"""1 - the union of the device-busy intervals over the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.device_events:
        return None
    w0, w1 = tr.window()
    return 100.0 * (1.0 - tr.busy_s() / (w1 - w0))
