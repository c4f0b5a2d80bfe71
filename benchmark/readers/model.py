"""Model (models/): the whole step's share of the chip's peak, and where
the device time goes by kind of operation."""

from benchmark import flops


def mfu(run):
    """Forward + backward FLOPs the layer table requires per image, times
    this run's images per second per chip, over the chip's peak.  The
    evaluation's forward passes are work of the window too but are not
    counted, so the share errs low."""
    per_image = flops.train_flops_per_image(run.config["layer_table"])
    rate = run.window.rate("images") / run.chips
    return 100.0 * per_image * rate / run.peak["flops_per_s"]


def nonconv_share(run):
    """Of the device time of the operations the compiled modules' text
    classes, the share in those that hold no convolution or dot."""
    if not run.trace.get("classed_s") or not run.trace.get("matmul_s"):
        return None
    return 100.0 * (1.0 - run.trace["matmul_s"] / run.trace["classed_s"])


def conv_roofline_share(run):
    """The training FLOPs of the traced window over what the chip could do
    in the time its convolution-bearing operations of the train modules
    took.  Compute-bound at these shapes: the bound is FLOPs over peak
    FLOP/s, not bytes."""
    if not run.trace.get("matmul_train_s"):
        return None
    per_image = flops.train_flops_per_image(run.config["layer_table"])
    per_chip_images = run.window.total("images") / run.chips
    least_s = per_image * per_chip_images / run.peak["flops_per_s"]
    return 100.0 * least_s / run.trace["matmul_train_s"]
