"""The compressed convs' share of their roofline in a bottleneck ResNet,
in %: Σ least time ÷ Σ device time over every compressed-conv kernel call
in the traced window, 1×1 and 3×3 alike (``costs_resnet``: least time per
call is the larger of its ops over the int8 peak and its bytes, the
residual included, over HBM bandwidth). A call is matched to its layer by
output shape, input channels and weight operand shape. Nothing matching
in the trace (a configuration without these layers, or a program without
these kernels): no reading."""
import costs_resnet


def read(run):
    if "stage_blocks" not in run.config:
        return None
    return costs_resnet.roofline_share(run, ("conv1x1", "conv3x3"))
