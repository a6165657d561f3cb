"""YOLOv5s backbone and SPPF as a plain layer list, as published:
Ultralytics YOLOv5 v6+, models/yolov5s.yaml (depth_multiple 0.33,
width_multiple 0.50), int8 inference.

Backbone: Conv 6x6/2 (32), Conv 3x3/2 (64), C3 x1 (64), Conv 3x3/2 (128),
C3 x2 (128), Conv 3x3/2 (256), C3 x3 (256), Conv 3x3/2 (512), C3 x1 (512),
then SPPF: Conv 1x1 (256), three chained 5x5/1 max-pools, concat (1024),
Conv 1x1 (512). Every Conv is conv, batch norm, activation. A C3 splits
into two 1x1 convs of half width, runs bottlenecks on the first (1x1
conv, 3x3 conv, then the shortcut add with nothing after it), concats and
fuses with a 1x1 conv.

Departures, forced, which the program follows too: ReLU in place of SiLU
(no int8 SiLU op); batch norm folded into the requant multiplier. The
PANet neck and the Detect head are left out (no upsample; one output).
"""

from reference import Net


def _c3(net: Net, name: str, x: str, cout: int, n: int) -> str:
    half = max(8, cout // 2)
    y1 = net.conv(f"{name}.cv1", x, half, 1)
    for i in range(n):
        z = net.conv(f"{name}.m{i}.cv1", y1, half, 1)
        z = net.conv(f"{name}.m{i}.cv2", z, half, 3)
        y1 = net.add(f"{name}.m{i}.add", z, y1)
    y2 = net.conv(f"{name}.cv2", x, half, 1)
    return net.conv(f"{name}.cv3", net.concat(f"{name}.cat", [y1, y2]),
                    cout, 1)


def network(h: int = 640, w: int = 640, width: float = 1.0) -> Net:
    net = Net(f"yolov5s_v6_{h}x{w}", (h, w, 3))

    def ch(c: int) -> int:
        return max(8, int(c * width))

    y = net.conv("stem", "input", ch(32), 6, stride=2, pad=2)
    for i, (c, n) in enumerate(((64, 1), (128, 2), (256, 3), (512, 1))):
        y = net.conv(f"d{i + 1}", y, ch(c), 3, stride=2)
        y = _c3(net, f"c3_{i + 1}", y, ch(c), n)
    y = net.conv("sppf.cv1", y, ch(512) // 2, 1)
    p1 = net.maxpool("sppf.p1", y, 5, 1, pad=2)
    p2 = net.maxpool("sppf.p2", p1, 5, 1, pad=2)
    p3 = net.maxpool("sppf.p3", p2, 5, 1, pad=2)
    y = net.concat("sppf.cat", [y, p1, p2, p3])
    net.conv("sppf.cv2", y, ch(512), 1)
    return net
