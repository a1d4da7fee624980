"""Where the Pallas kernels run: compiled by Mosaic on a TPU, under the
Pallas interpreter everywhere else.

This is the one place that decision is made. Every kernel entry point takes
``interpret=None`` and resolves it here: ``None`` means "interpret off-TPU,
compile on a TPU". An explicit ``interpret=True`` on a TPU host is refused —
the interpreter is the CPU test mode, never a quiet substitute for the chip.
An explicit ``interpret=False`` off-TPU is how the ahead-of-time compile
tests lower the kernels for a described (not attached) TPU.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None = None) -> bool:
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise ValueError("interpret=True runs the Pallas interpreter, a CPU "
                         "test mode; on a TPU the kernels run compiled")
    return bool(interpret)


def vmem_limit(buffer_bytes: int) -> int:
    """Scoped-VMEM request for a kernel whose pipelined blocks take
    ``buffer_bytes``: the blocks plus 16 MiB for in-kernel temporaries. A
    request past the chip's VMEM fails at compile time, not at run time."""
    return int(buffer_bytes) + (16 << 20)
