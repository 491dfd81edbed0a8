"""Seconds from the harness's start to the first timed query: graph
generation, `flip.compile`, the transfer to the device and the warm-up
call of the cell's own shape."""


def read(win):
    return win.setup_s
