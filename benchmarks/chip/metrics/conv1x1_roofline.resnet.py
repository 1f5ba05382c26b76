"""The 1×1 compressed convs' share of their roofline in a bottleneck
ResNet, in %: ``conv_roofline.resnet`` over the 1×1 calls alone (each
block's c1 and c3, and the projections), the pointwise path's own share.
No matching call in the trace: no reading."""
import costs_resnet


def read(run):
    if "stage_blocks" not in run.config:
        return None
    return costs_resnet.roofline_share(run, ("conv1x1",))
