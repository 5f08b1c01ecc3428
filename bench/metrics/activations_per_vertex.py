"""Direction and frontier: vertex activations per vertex reached, the
program's ``activations`` counter summed over the window's queries over
the vertices their answers reach (finite entries).  1.0 means every
vertex entered the frontier once; label-correcting relaxation reads
higher, once for each time it re-activates a settled vertex."""
import numpy as np

UNIT = "activations/vtx"


def read(window):
    counted = [q for q in window.queries
               if "activations" in (getattr(q, "counters", None) or {})]
    reached = sum(int(np.count_nonzero(np.isfinite(q.answer)))
                  for q in counted)
    if not reached:
        return None
    return sum(q.counters["activations"] for q in counted) / reached
