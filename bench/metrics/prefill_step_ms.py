"""Device milliseconds per ``arena_prefill`` program.  The program carries
no name of its own in the trace, so: of the compiled-program executions
that do not run the decode-attention kernel, the longest ones, as many as
the harness counted admitting steps (the others are the small token
scatter that follows each prefill)."""
from trace_reduce import containing, matching

KERNEL = r"^%?decode_attention(\.\d+)?( =|$)"  # see decode_attention_roofline


def read(run):
    t = run.trace
    n = sum(1 for step in run.counters["steps"] if step[3])
    decode = set(containing(t.programs(), matching(t.ops(), KERNEL)))
    other = sorted((e for e in t.programs() if e not in decode),
                   key=lambda e: -e[2])[:n]
    if not n or len(other) < n:
        return None
    return sum(d for _, _, d in other) / n * 1e-6
