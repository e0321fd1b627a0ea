"""Device time of the ``core/device_sweep`` sweep programs
(``_stable_stats``, ``_trace_ldt``) per query, ms: the union of those
programs' executions on the device's ``XLA Modules`` line."""


def read(view):
    per_chip = view.module_seconds(r"_stable_stats|_trace_ldt")
    if not per_chip or max(per_chip) <= 0 or view.units == 0:
        return None
    return max(per_chip) / view.units * 1e3
