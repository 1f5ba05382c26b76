"""Process start to the first timed request: imports, weights, calibration,
plan building, compiling or loading each bucket's program, warm-up, and
the request pool."""


def read(run):
    return run.setup_s
