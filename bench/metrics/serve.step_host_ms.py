"""Host milliseconds per ``ContinuousBatchingEngine.step`` call until it
returns (admission, building the padded prefill inputs, dispatching the
prefill and decode programs), from the harness's ``serve.step`` span."""


def read(run):
    s = run.spans.in_window("serve.step")
    return sum(s) / len(s) * 1e3 if s else None
