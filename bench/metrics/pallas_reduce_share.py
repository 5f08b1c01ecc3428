"""Edge reduction: device time in the blocked Pallas segment reducers
(``seg_sum_pallas``, ``seg_minmax_pallas``: the custom calls carry the
names of the jitted entry points) over the device's busy time."""
UNIT = "%"
KERNELS = ("seg_sum_pallas", "seg_minmax_pallas")


def read(window):
    trace = window.trace
    if trace is None or not trace["busy_s"]:
        return None
    pallas = sum(t for name, t in trace["op_s"].items()
                 if name.startswith(KERNELS))
    return 100.0 * pallas / trace["busy_s"] if pallas else None
