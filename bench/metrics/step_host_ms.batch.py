"""Host time per engine step: the step's span less the device time inside it."""

from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    own = trace_reduce.span_self_times(run.trace, run.trace_window,
                                       "engine.step", run.busy_union)
    return 1e3 * sum(own) / len(own) if own else None
