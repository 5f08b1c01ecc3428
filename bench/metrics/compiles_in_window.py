"""Loop driver, compiling: XLA compiles plus persistent-cache loads that
began inside the window (JAX's backend-compile events)."""
UNIT = "compiles"


def read(window):
    return float(window.compiles) if window.queries else None
