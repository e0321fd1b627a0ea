"""Host planning per query, ms: time inside the harness's spans around
``engine.stable_plans``, ``engine.compile_trace`` and
``churn.paper_breakdown_trace``, over the traced window's queries."""


def read(view):
    sec = view.host_seconds(r"^bench\.plan\.")
    if sec <= 0 or view.units == 0:
        return None
    return sec / view.units * 1e3
