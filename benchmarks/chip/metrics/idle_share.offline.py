"""Share of the traced window in which no operation ran on the chip, in %
(averaged over chips)."""
import devtrace


def read(run):
    return None if run.trace is None else 100.0 * devtrace.idle_share(run.trace)
