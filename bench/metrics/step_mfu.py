"""The whole step's share of the chip's peak: the operations one image
needs (``bench/work.py``, real images only, not pad rows) times the images
per second of the run's window, over the peak for the configuration's
weight type."""


def read(run):
    if run.img_per_s <= 0:
        return None
    ops = 2 * sum(layer.macs for layer in run.layers(1))
    return 100.0 * ops * run.img_per_s / run.peaks[run.cell.config["peak"]]
