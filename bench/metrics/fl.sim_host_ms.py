"""Host milliseconds per round in ``HybridSimulation.run_plan_round``
(fleet sampling, the cohort loop of ``_run_split``, batch building and
emission), from the harness's ``fl.sim`` span around each call."""


def read(run):
    s = run.spans.in_window("fl.sim")
    return sum(s) / len(s) * 1e3 if s else None
