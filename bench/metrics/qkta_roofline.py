"""The QKTA kernel's share of its roofline: the least time of the QKTA
mask layers (``bench/work.py`` family "qkta": they have no MACs, so it is
their bytes, packed Q and K read and X' written, over the HBM bandwidth)
at each traced step's bucket, over the device time of the
``qk_token_attention`` kernel in those steps. A program without that
kernel reports nothing."""

# the kernel's custom call, whether or not the trace reduction lists it
# among its kernel families (``bench/trace_reduce.py:op_family``)
KERNELS = ("qk_token_attention", "xla:qk_token_attention")


def read(run):
    if run.trace is None:
        return None
    least = kernel_ns = 0.0
    for m in run.trace.modules:
        ns = sum(m.family_ns.get(k, 0.0) for k in KERNELS)
        if m.bucket is None or ns <= 0:
            continue
        t, _ = run.least_time_s([layer for layer in run.layers(m.bucket)
                                 if layer.family == "qkta"])
        least += t
        kernel_ns += ns
    if kernel_ns <= 0:
        return None
    return 100.0 * least / (kernel_ns / 1e9)
