"""Seconds the server's warm-up spent tracing, compiling (or loading from
the compile cache) and first running every bucket (``ServerStats.warmup_s``,
host clock), a part of ``setup_s``. A server without the counter: no
reading."""


def read(run):
    return getattr(run.stats, "warmup_s", None) or None
