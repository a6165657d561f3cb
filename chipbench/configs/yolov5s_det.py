"""YOLOv5s as published, whole, as a plain layer list: Ultralytics YOLOv5
v6+, models/yolov5s.yaml (depth_multiple 0.33, width_multiple 0.50),
int8 inference.

Backbone (layers 0-9): Conv 6x6/2 (32), Conv 3x3/2 (64), C3 x1 (64),
Conv 3x3/2 (128), C3 x2 (128), Conv 3x3/2 (256), C3 x3 (256),
Conv 3x3/2 (512), C3 x1 (512), SPPF (Conv 1x1 256, three chained 5x5/1
max-pools, concat 1024, Conv 1x1 512).
PANet neck (layers 10-23): Conv 1x1 (256), upsample x2, concat with
layer 6, C3 x1 (256, no shortcut), Conv 1x1 (128), upsample x2, concat
with layer 4, C3 x1 (128, no shortcut) = P3; Conv 3x3/2 (128), concat
with layer 14, C3 x1 (256, no shortcut) = P4; Conv 3x3/2 (256), concat
with layer 10, C3 x1 (512, no shortcut) = P5.
Detect: one 1x1 conv of 255 channels (3 anchors x 85) on each of P3, P4
and P5, no activation; the network's outputs, in that order.

Every Conv is conv, batch norm, SiLU. A C3 splits into two 1x1 convs of
half width, runs bottlenecks on the first (1x1 conv, 3x3 conv, then the
shortcut add with nothing after it, where the C3 has shortcuts), concats
and fuses with a 1x1 conv.

Departures, which the program follows too: batch norm folded into the
requant multiplier, and no conv bias (Detect's included); SiLU is the
int8 table of `reference.silu` at one activation scale for every layer.
Detect's sigmoid, grid and anchor decode and NMS are host
post-processing, outside the network.
"""

from reference import Net

DETECT_CHANNELS = 255


def _conv(net: Net, name: str, x: str, cout: int, k: int, scale: float,
          stride: int = 1, pad: int | None = None) -> str:
    y = net.conv(name, x, cout, k, stride=stride, pad=pad, relu=False)
    return net.silu(f"{name}.silu", y, scale)


def _c3(net: Net, name: str, x: str, cout: int, n: int, scale: float,
        shortcut: bool = True) -> str:
    half = max(8, cout // 2)
    y1 = _conv(net, f"{name}.cv1", x, half, 1, scale)
    for i in range(n):
        z = _conv(net, f"{name}.m{i}.cv1", y1, half, 1, scale)
        z = _conv(net, f"{name}.m{i}.cv2", z, half, 3, scale)
        y1 = net.add(f"{name}.m{i}.add", z, y1) if shortcut else z
    y2 = _conv(net, f"{name}.cv2", x, half, 1, scale)
    return _conv(net, f"{name}.cv3", net.concat(f"{name}.cat", [y1, y2]),
                 cout, 1, scale)


def network(h: int = 640, w: int = 640, width: float = 1.0,
            silu_scale: float = 0.0625) -> Net:
    net = Net(f"yolov5s_det_{h}x{w}", (h, w, 3))
    s = silu_scale

    def ch(c: int) -> int:
        return max(8, int(c * width))

    y = _conv(net, "stem", "input", ch(32), 6, s, stride=2, pad=2)
    taps = []
    for i, (c, n) in enumerate(((64, 1), (128, 2), (256, 3), (512, 1))):
        y = _conv(net, f"d{i + 1}", y, ch(c), 3, s, stride=2)
        y = _c3(net, f"c3_{i + 1}", y, ch(c), n, s)
        taps.append(y)
    y = _conv(net, "sppf.cv1", y, ch(512) // 2, 1, s)
    p1 = net.maxpool("sppf.p1", y, 5, 1, pad=2)
    p2 = net.maxpool("sppf.p2", p1, 5, 1, pad=2)
    p3 = net.maxpool("sppf.p3", p2, 5, 1, pad=2)
    y = _conv(net, "sppf.cv2", net.concat("sppf.cat", [y, p1, p2, p3]),
              ch(512), 1, s)

    h10 = _conv(net, "h10", y, ch(256), 1, s)
    y = net.concat("h12", [net.upsample("h11", h10, 2), taps[2]])
    y = _c3(net, "h13", y, ch(256), 1, s, shortcut=False)
    h14 = _conv(net, "h14", y, ch(128), 1, s)
    y = net.concat("h16", [net.upsample("h15", h14, 2), taps[1]])
    out3 = _c3(net, "h17", y, ch(128), 1, s, shortcut=False)
    y = _conv(net, "h18", out3, ch(128), 3, s, stride=2)
    out4 = _c3(net, "h20", net.concat("h19", [y, h14]), ch(256), 1, s,
               shortcut=False)
    y = _conv(net, "h21", out4, ch(256), 3, s, stride=2)
    out5 = _c3(net, "h23", net.concat("h22", [y, h10]), ch(512), 1, s,
               shortcut=False)
    for i, p in enumerate((out3, out4, out5)):
        net.mark_output(net.conv(f"detect.m{i}", p, DETECT_CHANNELS, 1,
                                 relu=False))
    return net
