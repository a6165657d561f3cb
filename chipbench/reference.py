"""Plain reference for the benchmark's int8 CNNs, independent of the program.

A configuration's network is written out once as a `Net`: a flat list of
layers with their shapes, built by the reference module beside the
configuration's file (`configs/resnet.py`, `configs/yolov5s_v6.py`).
`forward` evaluates it on one frame in numpy, op by op, with no partition,
schedule, kernel or batching, and returns every output of the network by
name: the tensors marked with `Net.mark_output`, in the order marked, or
the last layer's output where none is marked.

* conv and fully-connected layers are im2col matrix products in float64.
  Every product of two int8 values and every sum of up to 2**38 of them is
  an integer below 2**53, so float64 holds the int32 accumulator exactly
  (and uses the host's BLAS, which int32 matmul would not).
* requant is the float32 product of the int32 accumulator with the layer's
  float32 multiplier, rounded half to even and clamped to int8.
* add saturates to int8; max-pool pads with -128; global average pool
  rounds the float64 mean half to even.
* upsample repeats each pixel `factor` times along both spatial axes
  (nearest neighbour, `nn.Upsample(scale_factor=f, mode="nearest")`).
* silu maps each int8 value q through one 256-entry table that keeps the
  input's scale s (a layer attribute):
  t[q] = clip(rint(q * sigmoid(q * s)), -128, 127), computed in float64
  with sigmoid(z) = 1 / (1 + exp(-z)) and rint rounding half to even.
  A program reproduces this table bit for bit.

`arith="int4"` is the control: the same network with every conv and
fully-connected input and weight first rounded to int4 (a step of 16,
clamped to [-8, 7]) and the product scaled back by 256, the precision one
step below the configuration's int8. Every other op, silu's table
included, is the same in both.

The parameter names (`<conv>.w`, `<conv>.rq.mult`, `<fc>.w`) and the
weight layout (rows ordered kernel-row, kernel-column, input channel) are
the interface to the program: the harness checks that the program's graph
asks for exactly these names and shapes.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Layer:
    op: str     # conv | fc | add | relu | maxpool | gap | concat | upsample | silu
    name: str
    inputs: tuple[str, ...]
    output: str
    attrs: dict


class Net:
    """A network as a list of layers over named tensors of known shape."""

    def __init__(self, name: str, input_shape: tuple[int, int, int]):
        self.name = name
        self.layers: list[Layer] = []
        self.shapes: dict[str, tuple[int, ...]] = {"input": input_shape}
        self.dtypes: dict[str, str] = {"input": "int8"}
        self.weights: dict[str, tuple[int, int]] = {}   # name -> shape
        self.mults: dict[str, int] = {}                 # name -> fan-in
        self.output = "input"             # the last layer's output
        self._marked: list[str] = []

    @property
    def outputs(self) -> list[str]:
        """The network's outputs: those marked, or else the last layer's."""
        return list(self._marked) or [self.output]

    def mark_output(self, t: str) -> str:
        """Make tensor `t` one of the network's outputs."""
        if t not in self.shapes:
            raise ValueError(f"no tensor {t!r} in {self.name}")
        if t not in self._marked:
            self._marked.append(t)
        return t

    def _add(self, op, name, inputs, shape, dtype="int8", **attrs) -> str:
        out = f"{name}.out"
        self.layers.append(Layer(op, name, tuple(inputs), out, attrs))
        self.shapes[out] = tuple(shape)
        self.dtypes[out] = dtype
        self.output = out
        return out

    def conv(self, name: str, x: str, cout: int, k: int, stride: int = 1,
             pad: int | None = None, relu: bool = True) -> str:
        """conv -> requant to int8 -> optional relu."""
        h, w, cin = self.shapes[x]
        p = k // 2 if pad is None else pad
        oh, ow = (h + 2 * p - k) // stride + 1, (w + 2 * p - k) // stride + 1
        self.weights[f"{name}.w"] = (k * k * cin, cout)
        self.mults[f"{name}.rq.mult"] = k * k * cin
        y = self._add("conv", name, [x], (oh, ow, cout), k=k, stride=stride,
                      pad=p, relu=relu)
        return y

    def fc(self, name: str, x: str, n: int) -> str:
        """(1, K) int8 @ (K, n) int8 -> int32 logits, no requant."""
        _, k = self.shapes[x]
        self.weights[f"{name}.w"] = (k, n)
        return self._add("fc", name, [x], (1, n), dtype="int32")

    def add(self, name: str, a: str, b: str) -> str:
        return self._add("add", name, [a, b], self.shapes[a])

    def relu(self, name: str, x: str) -> str:
        return self._add("relu", name, [x], self.shapes[x])

    def maxpool(self, name: str, x: str, k: int, stride: int,
                pad: int = 0) -> str:
        h, w, c = self.shapes[x]
        oh, ow = (h + 2 * pad - k) // stride + 1, (w + 2 * pad - k) // stride + 1
        return self._add("maxpool", name, [x], (oh, ow, c), k=k,
                         stride=stride, pad=pad)

    def gap(self, name: str, x: str) -> str:
        return self._add("gap", name, [x], (1, self.shapes[x][2]))

    def concat(self, name: str, xs: list[str]) -> str:
        shape = self.shapes[xs[0]][:-1] + (sum(self.shapes[t][-1] for t in xs),)
        return self._add("concat", name, xs, shape)

    def upsample(self, name: str, x: str, factor: int) -> str:
        """Nearest-neighbour upsampling by an integer factor."""
        h, w, c = self.shapes[x]
        return self._add("upsample", name, [x], (h * factor, w * factor, c),
                         factor=factor)

    def silu(self, name: str, x: str, scale: float) -> str:
        """int8 SiLU by table at the input's scale (module docstring)."""
        if self.dtypes[x] != "int8":
            raise ValueError(f"silu {name!r} reads {x!r}, which is not int8")
        return self._add("silu", name, [x], self.shapes[x], scale=scale)

    def mult_values(self, gain: float) -> dict[str, np.float32]:
        """Requant multipliers gain / sqrt(fan-in): with a gain fitted to
        the network, random int8 activations neither die out nor saturate
        through its depth."""
        return {n: np.float32(gain / np.sqrt(k)) for n, k in self.mults.items()}


def _q4(v: np.ndarray) -> np.ndarray:
    return np.clip(np.round(v.astype(np.float64) / 16), -8, 7)


def _im2col(x: np.ndarray, k: int, stride: int, pad: int) -> np.ndarray:
    h, w, c = x.shape
    xp = np.pad(x, ((pad, pad), (pad, pad), (0, 0)))
    oh, ow = (h + 2 * pad - k) // stride + 1, (w + 2 * pad - k) // stride + 1
    win = np.lib.stride_tricks.sliding_window_view(xp, (k, k), axis=(0, 1))
    win = win[:oh * stride:stride, :ow * stride:stride]     # (oh, ow, c, k, k)
    return win.transpose(0, 1, 3, 4, 2).reshape(oh * ow, k * k * c)


def _matmul(x: np.ndarray, w: np.ndarray, arith: str) -> np.ndarray:
    if arith == "int4":
        return (_q4(x) @ _q4(w)) * 256.0
    return x.astype(np.float64) @ w.astype(np.float64)


def _requant(acc: np.ndarray, mult: np.float32) -> np.ndarray:
    y = np.round(acc.astype(np.float32) * np.float32(mult))
    return np.clip(y, -128, 127).astype(np.int8)


def silu_table(scale: float) -> np.ndarray:
    """t[q + 128] for q in -128..127: the int8 SiLU at input scale `scale`."""
    q = np.arange(-128, 128, dtype=np.float64)
    sigmoid = 1.0 / (1.0 + np.exp(-q * np.float64(scale)))
    y = np.rint(q * sigmoid)
    return np.clip(y, -128, 127).astype(np.int8)


def _maxpool(x: np.ndarray, k: int, stride: int, pad: int) -> np.ndarray:
    xp = np.pad(x, ((pad, pad), (pad, pad), (0, 0)), constant_values=-128)
    h, w, _ = xp.shape
    oh, ow = (h - k) // stride + 1, (w - k) // stride + 1
    out = np.full((oh, ow, x.shape[2]), -128, np.int8)
    for i in range(k):
        for j in range(k):
            out = np.maximum(out, xp[i:i + oh * stride:stride,
                                     j:j + ow * stride:stride])
    return out


def forward(net: Net, params: dict, frame: np.ndarray,
            arith: str = "int8") -> dict[str, np.ndarray]:
    """The network's outputs for one (H, W, C) int8 frame, by name in the
    order of `net.outputs`; `params` holds the weights and the requant
    multipliers by name."""
    if arith not in ("int8", "int4"):
        raise ValueError(f"arith must be int8 or int4, not {arith!r}")
    vals = {"input": np.asarray(frame, np.int8)}
    for ly in net.layers:
        xs = [vals[t] for t in ly.inputs]
        a = ly.attrs
        if ly.op == "conv":
            cols = _im2col(xs[0], a["k"], a["stride"], a["pad"])
            acc = _matmul(cols, params[f"{ly.name}.w"], arith)
            y = _requant(acc, params[f"{ly.name}.rq.mult"])
            y = y.reshape(net.shapes[ly.output])
            vals[ly.output] = np.maximum(y, 0) if a["relu"] else y
        elif ly.op == "fc":
            acc = _matmul(xs[0], params[f"{ly.name}.w"], arith)
            vals[ly.output] = acc.astype(np.int32)
        elif ly.op == "add":
            s = xs[0].astype(np.int32) + xs[1].astype(np.int32)
            vals[ly.output] = np.clip(s, -128, 127).astype(np.int8)
        elif ly.op == "relu":
            vals[ly.output] = np.maximum(xs[0], 0)
        elif ly.op == "maxpool":
            vals[ly.output] = _maxpool(xs[0], a["k"], a["stride"], a["pad"])
        elif ly.op == "gap":
            m = np.round(xs[0].astype(np.int32).mean(axis=(0, 1)))
            vals[ly.output] = np.clip(m, -128, 127).astype(np.int8)[None]
        elif ly.op == "concat":
            vals[ly.output] = np.concatenate(xs, axis=-1)
        elif ly.op == "upsample":
            f = a["factor"]
            vals[ly.output] = np.repeat(np.repeat(xs[0], f, axis=0), f, axis=1)
        elif ly.op == "silu":
            table = silu_table(a["scale"])
            vals[ly.output] = table[xs[0].astype(np.int16) + 128]
        else:
            raise ValueError(f"unknown layer op {ly.op!r}")
    return {t: vals[t] for t in net.outputs}
