"""Operations and bytes a frame needs, counted from the reference's shapes.

`frame_ops` counts the matrix-unit work: 2 operations (a multiply and an
add) per multiply-accumulate of every conv and fully-connected layer.
Requant, ReLU, SiLU, add, pooling, upsample and concat run on the vector
unit or only move data; they count 0, so a share of the int8 peak built
on this count never credits them as matrix work.

`call_floor_s` is the least time one call of the program can take on a
chip, whatever implements it: the larger of its matrix work over the int8
peak and of the bytes it cannot avoid moving over the HBM bandwidth. The
bytes it cannot avoid are the weights, read once per call whatever the
batch, and each frame's input and every one of its outputs. Intermediate
activations are not counted: a program that keeps them on chip moves
none of them, so a count that included them could put a sound program
above its roofline.
"""

from __future__ import annotations

import math

from reference import Net

DTYPE_BYTES = {"int8": 1, "int32": 4}


def layer_ops(net: Net, layer) -> int:
    if layer.op == "conv":
        oh, ow, cout = net.shapes[layer.output]
        return 2 * oh * ow * net.weights[f"{layer.name}.w"][0] * cout
    if layer.op == "fc":
        k, n = net.weights[f"{layer.name}.w"]
        return 2 * k * n
    return 0


def frame_ops(net: Net) -> int:
    return sum(layer_ops(net, ly) for ly in net.layers)


def weight_bytes(net: Net) -> int:
    """int8 weights, one byte each."""
    return sum(math.prod(s) for s in net.weights.values())


def frame_io_bytes(net: Net) -> int:
    """One frame's input and outputs as the program reads and writes them."""
    return math.prod(net.shapes["input"]) + sum(
        math.prod(net.shapes[t]) * DTYPE_BYTES[net.dtypes[t]]
        for t in net.outputs)


def call_floor_s(net: Net, frames: int, peaks: dict) -> float:
    """Least device time of one call serving `frames` frames."""
    compute = frames * frame_ops(net) / peaks["int8_ops_per_s"]
    memory = (weight_bytes(net) + frames * frame_io_bytes(net)) \
        / peaks["hbm_bytes_per_s"]
    return max(compute, memory)
