"""Operations and bytes of Spikformer's layers, counted from shapes alone.

The count is the work the algorithm needs, the same whatever implements a
layer: a later change that moves a layer between the byte-LUT gather and
the unpack-dot kernel, or fuses two layers, changes the time and leaves
this yardstick alone.

* One MAC is one spike (or pixel) times one weight; an operation count is
  two per MAC.
* Binary layers run once per live timestep (T), the first convolution
  (SSSC on the 8-bit image) once, since the image does not change over T.
* STDP attention counts both products, (Q K^T) and (scores V), per head
  and per timestep.
* Bytes of a spiking matmul: its packed input (ceil(T/8) bytes per input
  neuron), its int8 weights, and its packed output.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Layer:
    path: str       # "scs/conv1", "blocks/b3/mlp/fc1", "blocks/b3/ssa/stdp"
    family: str     # "stem" | "qkvo" | "mlp" | "stdp" | "head"
    macs: int
    bytes: int      # 0 where no roofline is taken (stdp, head)
    matmul: bool    # a spiking matmul (counted by the matmul roofline)


def _groups(t: int) -> int:
    return -(-t // 8)


def spikformer_layers(cfg: dict, batch: int = 1) -> list[Layer]:
    """Every layer of one forward pass over ``batch`` images, in call order.
    ``cfg`` holds the configuration file's sizes."""
    t, g = cfg["timesteps"], _groups(cfg["timesteps"])
    side = cfg["img_size"]
    cin = cfg["in_channels"]
    out = []
    for i, cout in enumerate(cfg["scs_channels"]):
        side //= 2
        m = batch * side * side
        k = 4 * cin
        if i == 0:      # SSSC: 8-bit pixels, once for all T
            macs, in_bytes = m * k * cout, m * k
        else:
            macs, in_bytes = t * m * k * cout, g * m * k
        out.append(Layer(f"scs/conv{i}", "stem", macs,
                         in_bytes + k * cout + g * m * cout, True))
        cin = cout
    d, n_tok, heads = cfg["dim"], side * side, cfg["heads"]
    hidden = d * cfg["mlp_ratio"]
    m = batch * n_tok
    dh = d // heads

    def linear(path, family, k, n):
        return Layer(path, family, t * m * k * n,
                     g * m * k + k * n + g * m * n, True)

    for b in range(cfg["depth"]):
        p = f"blocks/b{b}"
        out += [linear(f"{p}/ssa/{w}", "qkvo", d, d) for w in ("wq", "wk", "wv")]
        out.append(Layer(f"{p}/ssa/stdp", "stdp",
                         2 * t * batch * heads * n_tok * n_tok * dh, 0, False))
        out.append(linear(f"{p}/ssa/wo", "qkvo", d, d))
        out.append(linear(f"{p}/mlp/fc1", "mlp", d, hidden))
        out.append(linear(f"{p}/mlp/fc2", "mlp", hidden, d))
    out.append(Layer("head", "head", batch * d * cfg["num_classes"], 0, False))
    return out


def macs_by_family(layers) -> dict:
    fam: dict[str, int] = {}
    for layer in layers:
        fam[layer.family] = fam.get(layer.family, 0) + layer.macs
    return fam


def ops(layers) -> int:
    return 2 * sum(layer.macs for layer in layers)


def least_time_s(layers, *, ops_per_s: float, bytes_per_s: float):
    """The roofline's least time for ``layers``, each bounded by the larger
    of its operations over the peak rate and its bytes over the bandwidth.
    Returns ``(seconds, compute_bound_share)``: the share of that least
    time spent in layers that the compute peak bounds."""
    total = compute = 0.0
    for layer in layers:
        tc = 2 * layer.macs / ops_per_s
        tm = layer.bytes / bytes_per_s
        total += max(tc, tm)
        if tc >= tm:
            compute += tc
    return total, (compute / total if total else 0.0)
