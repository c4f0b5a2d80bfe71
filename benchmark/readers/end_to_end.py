"""End-to-end metrics: taken by the harness itself from the host clock."""


def train_img_s_chip(run):
    """Every image whose optimizer step finished inside the window, over
    the seconds between the fence that opens it and the fence that closes
    it, over the chips."""
    return run.window.rate("images") / run.chips


def setup_s(run):
    """Process start to the opening fence."""
    return run.setup_s
