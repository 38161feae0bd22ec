"""Device time of prefill programs as a share of the traced window."""

from bench import readers


def read(run):
    t = readers.device_time(run, "device_modules", readers.is_prefill_module)
    if t is None:
        return None
    w = run.trace_window
    return 100.0 * t / ((w[1] - w[0]) * 1e-9)
