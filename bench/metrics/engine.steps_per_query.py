"""Mean fixpoint steps of a traversal (`QueryResult.steps`, exact), over
every row of the window's completed calls."""


def read(win):
    rows = [s for c in win.done for s in c.steps]
    return sum(rows) / len(rows) if rows else None
