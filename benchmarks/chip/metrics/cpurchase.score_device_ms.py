"""Device time of the `cpurchase_score` scope (the gather, the rule scores
and the segment sum; the runner sums it over the traced window,
``scope_s``) per execution of the jitted step (`jit_names` of the
configuration) in the trace; nothing without either."""


def read(run):
    trace = run.get("trace") or {}
    runs = (trace.get("module_runs") or {}).get("jit_" + run["config"]["jit_names"][-1], 0)
    seconds = run.get("scope_s") or 0.0
    if not runs or seconds <= 0:
        return None
    return 1e3 * seconds / runs
