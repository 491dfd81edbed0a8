"""Host milliseconds per fixpoint step: the window's query walls over
its fixpoint steps (a batched call's steps are those of its longest
row, the loop's trip count)."""


def read(win):
    steps = sum(c.iterations for c in win.done)
    return 1e3 * sum(c.wall_s for c in win.done) / steps if steps else None
