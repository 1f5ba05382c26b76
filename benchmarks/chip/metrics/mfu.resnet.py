"""Whole-step share of the chip's peak for a bottleneck ResNet, in %:
images per second per chip times the time one image takes at peak
(``costs_resnet.peak_time_per_image_s``: Σ over the served layers of its
ops over its peak, int8 for the compressed convs and the head, bf16 for
the fp32 stem). The rate is the window's own."""
import costs_resnet
import images_per_s


def read(run):
    if "stage_blocks" not in run.config:
        return None
    per_chip = images_per_s.read(run) / run.chips
    return 100.0 * per_chip * costs_resnet.peak_time_per_image_s(run.config, run.peaks)
