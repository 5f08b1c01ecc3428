"""Direction and frontier: share of iterations that ran the gathered
O(m_f) path, from each query's ``RunResult.occupancy_trace``."""
UNIT = "%"


def read(window):
    traced = [q for q in window.queries if q.occupancy_trace is not None]
    total = sum(len(q.occupancy_trace) for q in traced)
    if not total:
        return None
    sparse = sum(1 for q in traced for o in q.occupancy_trace if o >= 0.0)
    return 100.0 * sparse / total
