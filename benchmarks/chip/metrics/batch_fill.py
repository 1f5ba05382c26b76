"""Real samples over bucket slots dispatched, in %: 1 − the server's
``padded_frac`` (``ServerStats``), the share of device work not spent on
padding."""


def read(run):
    s = run.stats
    if not s.served_samples:
        return None
    return 100.0 * (s.served_samples - s.padded_samples) / s.served_samples
