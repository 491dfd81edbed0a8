"""Weight-block bytes streamed from HBM per traversed edge: the
program's telemetry (`hbm_weight_bytes_est`, blocks fetched times the
block's bytes) summed over the traced window, over the edges `teps`
counts there."""


def read(win):
    done = [c for c in win.done if c.weight_bytes is not None]
    edges = sum(c.edges for c in done)
    if not done or not edges:
        return None
    return sum(c.weight_bytes for c in done) / edges
