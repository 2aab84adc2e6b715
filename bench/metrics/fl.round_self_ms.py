"""Host milliseconds per round that ``run_plan_round`` spends outside the
message plane: each ``simdc.fl.round`` span less the outermost
``simdc.flow.*`` spans inside it.  What is left is fleet sampling, the cohort
chunk loop (slices, rng splits, cohort dispatches, emission building) and
the q_i rows' materialization."""
from program_spans import in_window, named


def read(run):
    got = in_window(run)
    if got is None:
        return None
    rec, spans = got
    rounds = named(spans, "fl.round")
    if not rounds:
        return None
    ns = sum(r.ns - sum(f.ns for f in rec.outermost(rec.subtree(r), "flow."))
             for r in rounds)
    return ns / len(rounds) * 1e-6
