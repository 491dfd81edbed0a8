"""Peak device memory in use by the end of the window, in 10^9 bytes (the
fullest chip's `memory_stats()["peak_bytes_in_use"]`)."""


def read(win):
    if win.memory_peak_bytes is None:
        return None
    return win.memory_peak_bytes / 1e9
