"""YOLOv5s backbone and SPPF as a plain layer list: Ultralytics YOLOv5
v6+, models/yolov5s.yaml (depth_multiple 0.33, width_multiple 0.50),
int8 inference.

Backbone as published: Conv 6x6/2 (32), Conv 3x3/2 (64), C3 x1 (64),
Conv 3x3/2 (128), C3 x2 (128), Conv 3x3/2 (256), C3 x3 (256),
Conv 3x3/2 (512), C3 x1 (512). A C3 splits into two 1x1 convs of half
width, runs bottlenecks (1x1, 3x3, shortcut add) on the first, concats
and fuses with a 1x1 conv.

Departures, all of them in the program that is measured, so the
reference follows them: ReLU in place of SiLU; batch norm folded into the
requant multiplier; in a bottleneck the 3x3 conv has no activation and
ReLU follows the add (published: SiLU on the 3x3, nothing after the add);
SPPF pools the 512-channel input itself and fuses the 2048-channel concat
(published: a 1x1 conv to 256 channels first, a 1024-channel concat).
The PANet neck and the Detect head are left out.
"""

from reference import Net


def _c3(net: Net, name: str, x: str, cout: int, n: int) -> str:
    half = max(8, cout // 2)
    y1 = net.conv(f"{name}.cv1", x, half, 1)
    for i in range(n):
        z = net.conv(f"{name}.m{i}.cv1", y1, half, 1)
        z = net.conv(f"{name}.m{i}.cv2", z, half, 3, relu=False)
        y1 = net.relu(f"{name}.m{i}.relu", net.add(f"{name}.m{i}.add", z, y1))
    y2 = net.conv(f"{name}.cv2", x, half, 1)
    return net.conv(f"{name}.cv3", net.concat(f"{name}.cat", [y1, y2]),
                    cout, 1)


def network(h: int = 640, w: int = 640, width: float = 1.0) -> Net:
    net = Net(f"yolov5s_{h}x{w}", (h, w, 3))

    def ch(c: int) -> int:
        return max(8, int(c * width))

    y = net.conv("stem", "input", ch(32), 6, stride=2, pad=2)
    for i, (c, n) in enumerate(((64, 1), (128, 2), (256, 3), (512, 1))):
        y = net.conv(f"d{i + 1}", y, ch(c), 3, stride=2)
        y = _c3(net, f"c3_{i + 1}", y, ch(c), n)
    p1 = net.maxpool("sppf.p1", y, 5, 1, pad=2)
    p2 = net.maxpool("sppf.p2", p1, 5, 1, pad=2)
    p3 = net.maxpool("sppf.p3", p2, 5, 1, pad=2)
    y = net.concat("sppf.cat", [y, p1, p2, p3])
    net.conv("sppf.cv", y, ch(512), 1)
    return net
