"""Whole-step share of the chip's peak, in %: images per second per chip
times the time one image takes at peak (Σ over layers of its ops over its
peak: int8 for the compressed convs and the head, bf16 for the fp32 stem,
which has no published fp32 peak). Ops from ``costs``; the rate is the
traced window's own."""
import costs
import images_per_s


def read(run):
    per_chip = images_per_s.read(run) / run.chips
    return 100.0 * per_chip * costs.peak_time_per_image_s(run.config, run.peaks)
