"""Loop driver: mean ``RunResult.iterations`` over the window's queries."""
UNIT = "iterations"


def read(window):
    iters = [q.iterations for q in window.queries]
    return sum(iters) / len(iters) if iters else None
