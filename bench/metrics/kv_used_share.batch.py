"""Pages holding tokens over pages reserved, averaged over window steps."""


def read(run):
    shares = [s.used_pages / s.reserved_pages for s in run.window_steps()
              if s.reserved_pages]
    return 100.0 * sum(shares) / len(shares) if shares else None
