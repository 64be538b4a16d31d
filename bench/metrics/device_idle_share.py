"""Share of the traced window in which no operation ran on the device,
in %: one minus the union of the device's operation intervals over the
window."""


def read(ctx):
    t = ctx["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
