"""CNN graph builders for the paper's own evaluation targets (§IV.A:
"medium-sized convolutional neural networks like ResNet50 or YOLOv5-small"),
plus a tiny CNN for fast tests. All nets are int8 (conv/gemm accumulate in
int32, then requantize), batch=1 per-frame inference — the real-time setting
the paper targets.
"""

from __future__ import annotations

from .graph import (Graph, OpNode, conv2d, eltwise, global_avg_pool, linear,
                    pool2d, requant)


def _conv_block(g: Graph, name: str, x: str, c_out: int, k: int,
                stride: int = 1, relu: bool = True,
                padding: int | None = None) -> str:
    """conv -> requant(+folded BN) -> relu, the standard int8 inference unit."""
    y = conv2d(g, name, x, c_out, k, stride=stride, padding=padding)
    y = requant(g, f"{name}.rq", y)
    if relu:
        y = eltwise(g, f"{name}.relu", "relu", [y])
    return y


def concat(g: Graph, name: str, xs: list[str]) -> str:
    shapes = [g.tensors[t].shape for t in xs]
    c = sum(s[-1] for s in shapes)
    out_shape = shapes[0][:-1] + (c,)
    y = f"{name}.out"
    g.add_tensor(y, out_shape, g.tensors[xs[0]].dtype)
    g.add_op(OpNode(name, "concat", list(xs), [y]))
    return y


def small_cnn(h: int = 32, w: int = 32, c: int = 3,
              num_classes: int = 10) -> Graph:
    """Tiny int8 CNN used by unit/property tests (fast to schedule/replay)."""
    g = Graph("small_cnn")
    x = "input"
    g.add_tensor(x, (h, w, c), "int8", is_input=True)
    y = _conv_block(g, "conv1", x, 16, 3, stride=1)
    y = pool2d(g, "pool1", "maxpool", y, 2, 2)
    y = _conv_block(g, "conv2", y, 32, 3, stride=1)
    y = pool2d(g, "pool2", "maxpool", y, 2, 2)
    y = global_avg_pool(g, "gap", y)
    y = linear(g, "fc", y, num_classes)
    g.mark_output(y)
    g.validate()
    return g


def _bottleneck(g: Graph, name: str, x: str, c_mid: int, c_out: int,
                stride: int = 1, downsample: bool = False) -> str:
    """ResNet v1 bottleneck: 1x1 -> 3x3 -> 1x1(+4x), residual int8 add."""
    idn = x
    y = _conv_block(g, f"{name}.c1", x, c_mid, 1)
    y = _conv_block(g, f"{name}.c2", y, c_mid, 3, stride=stride)
    y = _conv_block(g, f"{name}.c3", y, c_out, 1, relu=False)
    if downsample:
        idn = _conv_block(g, f"{name}.ds", x, c_out, 1, stride=stride,
                          relu=False)
    y = eltwise(g, f"{name}.add", "add", [y, idn])
    return eltwise(g, f"{name}.relu", "relu", [y])


def resnet50(h: int = 224, w: int = 224, num_classes: int = 1000,
             width: float = 1.0, blocks: tuple = (3, 4, 6, 3)) -> Graph:
    """Standard ResNet50 (int8). `width`/`blocks` allow reduced smoke configs."""
    g = Graph(f"resnet50_{h}x{w}" + ("" if width == 1.0 else f"_w{width}"))
    x = "input"
    g.add_tensor(x, (h, w, 3), "int8", is_input=True)

    def ch(c):
        return max(8, int(c * width))

    y = _conv_block(g, "stem", x, ch(64), 7, stride=2, padding=3)
    y = pool2d(g, "stem.pool", "maxpool", y, 3, 2)
    mids = (ch(64), ch(128), ch(256), ch(512))
    for si, (n_blocks, c_mid) in enumerate(zip(blocks, mids)):
        c_out = c_mid * 4
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            y = _bottleneck(g, f"s{si}.b{bi}", y, c_mid, c_out,
                            stride=stride, downsample=(bi == 0))
    y = global_avg_pool(g, "gap", y)
    y = linear(g, "fc", y, num_classes)
    g.mark_output(y)
    g.validate()
    return g


def _c3(g: Graph, name: str, x: str, c_out: int, n: int,
        relu_after_add: bool = False) -> str:
    """YOLOv5 C3 module (CSP bottleneck with 3 convs). Its bottlenecks are
    the published ones (activation on the 3x3 conv, nothing after the
    shortcut add) unless `relu_after_add`, which moves the activation
    after the add."""
    c_h = max(8, c_out // 2)
    y1 = _conv_block(g, f"{name}.cv1", x, c_h, 1)
    for i in range(n):
        z = _conv_block(g, f"{name}.m{i}.cv1", y1, c_h, 1)
        z = _conv_block(g, f"{name}.m{i}.cv2", z, c_h, 3,
                        relu=not relu_after_add)
        y1 = eltwise(g, f"{name}.m{i}.add", "add", [z, y1])
        if relu_after_add:
            y1 = eltwise(g, f"{name}.m{i}.relu", "relu", [y1])
    y2 = _conv_block(g, f"{name}.cv2", x, c_h, 1)
    y = concat(g, f"{name}.cat", [y1, y2])
    return _conv_block(g, f"{name}.cv3", y, c_out, 1)


def _yolov5s(name: str, h: int, w: int, width: float,
             published: bool) -> Graph:
    g = Graph(name)
    x = "input"
    g.add_tensor(x, (h, w, 3), "int8", is_input=True)

    def ch(c):
        return max(8, int(c * width))

    y = _conv_block(g, "stem", x, ch(32), 6, stride=2, padding=2)
    for i, (c, n) in enumerate(((64, 1), (128, 2), (256, 3), (512, 1))):
        y = _conv_block(g, f"d{i + 1}", y, ch(c), 3, stride=2)
        y = _c3(g, f"c3_{i + 1}", y, ch(c), n, relu_after_add=not published)
    # SPPF (padded stride-1 maxpools keep spatial dims)
    if published:
        y = _conv_block(g, "sppf.cv1", y, ch(512) // 2, 1)
    p1 = pool2d(g, "sppf.p1", "maxpool", y, 5, 1, padding=2)
    p2 = pool2d(g, "sppf.p2", "maxpool", p1, 5, 1, padding=2)
    p3 = pool2d(g, "sppf.p3", "maxpool", p2, 5, 1, padding=2)
    y = concat(g, "sppf.cat", [y, p1, p2, p3])
    y = _conv_block(g, "sppf.cv2" if published else "sppf.cv", y, ch(512), 1)
    g.mark_output(y)
    g.validate()
    return g


def yolov5s(h: int = 640, w: int = 640, width: float = 1.0) -> Graph:
    """YOLOv5-small backbone + SPPF as published (Ultralytics YOLOv5 v6+
    `models/yolov5s.yaml`: width 0.5, depth 0.33 of YOLOv5l), int8.

    At `width=1.0` the widths are the published ones (32, 64, 128, 256,
    512), the C3 repeats (1, 2, 3, 1), the bottleneck activates its 3x3
    conv and adds nothing after the shortcut add, and SPPF reduces 512 to
    256 channels, pools three times 5x5 and fuses the 1024-channel concat
    back to 512. The part kept is 10.35 GOP a frame at 640x640, about 63%
    of the published 16.5 GFLOPs. Departures, each forced: ReLU in place
    of SiLU (the graph IR has no SiLU); batch norm folded into the requant
    multiplier (int8 inference); the PANet neck and the Detect head left
    out (the graph IR has no upsample).
    """
    return _yolov5s(f"yolov5s_v6_{h}x{w}", h, w, width, published=True)


def yolov5s_backbone(h: int = 640, w: int = 640,
                     width: float = 1.0) -> Graph:
    """The YOLOv5-small backbone + SPPF of `yolov5s` with two more
    departures from the published network, which the first benchmark
    configuration `chipbench/configs/yolov5s_640.json` and its reference
    `chipbench/configs/yolov5.py` pin: in a bottleneck the 3x3 conv has no
    activation and a ReLU follows the shortcut add; SPPF max-pools the
    512-channel input itself and fuses a 2048-channel concat.
    """
    return _yolov5s(f"yolov5s_{h}x{w}", h, w, width, published=False)
