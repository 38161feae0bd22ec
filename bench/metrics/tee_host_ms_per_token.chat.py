"""Host time in the trust domain's ingress and egress crypto per output token in the window."""


def read(run):
    crypto = sum(t1 - t0 for name, t0, t1 in run.spans
                 if name in ("tee.ingress", "tee.egress") and run.in_window(t0))
    tokens = sum(1 for r in run.requests for t in r.times if run.in_window(t))
    return 1e3 * crypto / tokens if crypto > 0 and tokens else None
