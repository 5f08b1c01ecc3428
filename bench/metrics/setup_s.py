"""End to end: seconds from process start to the window's start
(generation, ingest, compiling or cache loads, the warm-up query)."""
UNIT = "s"


def read(window):
    return window.setup_s
