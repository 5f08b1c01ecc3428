"""End to end: traversed edges per second, the work of every query the
window answered (by the cell's counting rule) over the window's seconds."""
UNIT = "edges/s"


def read(window):
    return window.work / window.seconds if window.queries else None
