"""Traversed edges per second over the window (Graph500's TEPS, taken
over all the work and all the time of the window): the undirected edges
of every completed traversal's component, a batch of B counting B
traversals, over the window's seconds."""


def read(win):
    return sum(c.edges for c in win.done) / win.window_s
