"""Share of the traced window in which no operation ran on the device,
in %: 1 minus the union of the device's op intervals over the window,
averaged over the chips used."""


def read(win):
    if win.trace is None or not win.trace.busy_s:
        return None
    return 100.0 * (1.0 - win.trace.busy_s / win.trace.window_s)
