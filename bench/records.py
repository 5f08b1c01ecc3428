"""What a run records: each answered query, and the measured window that
every metric reader (``bench/metrics/<metric>.py``) reads."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Query:
    """One answered query: what was asked, the answer on the host, and the
    program's own counts."""
    query: object
    answer: object
    iterations: int
    converged: bool
    occupancy_trace: object


@dataclasses.dataclass
class Window:
    """The measured window: its queries and seconds, the backend compiles
    inside it, the reduced device trace (traced runs), the work the
    queries did by the cell's counting rule, and the run's set-up time."""
    queries: list
    seconds: float
    compiles: int
    trace: object = None
    work: float = 0.0
    setup_s: float = 0.0
