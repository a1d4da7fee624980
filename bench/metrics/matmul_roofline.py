"""The spiking matmuls' share of their roofline: the least time of the
work of every spiking matmul layer (``bench/work.py``: max of operations
over the peak and bytes over the bandwidth, per layer, at each traced
step's bucket, pad rows included since the device computes them) over the
device time of the matmul kernels in those steps. Counted the same way
whatever route or fusion the plan picked."""

KERNELS = ("spike_matmul", "lut_gather_matmul", "tflif_lut_matmul")


def read(run):
    if run.trace is None:
        return None
    least = kernel_ns = 0.0
    for m in run.trace.modules:
        ns = sum(m.family_ns.get(k, 0.0) for k in KERNELS)
        if m.bucket is None or ns <= 0:
            continue
        layers = [layer for layer in run.layers(m.bucket) if layer.matmul]
        t, _ = run.least_time_s(layers)
        least += t
        kernel_ns += ns
    if kernel_ns <= 0:
        return None
    return 100.0 * least / (kernel_ns / 1e9)
