"""ResNet-50 as a plain layer list: He et al. 2016 (arXiv:1512.03385),
Table 1, "50-layer" column, int8 inference.

Each conv is conv -> requant (batch norm folded into the multiplier) ->
ReLU. Bottleneck: 1x1 -> 3x3 (carrying the stage's stride, as in the
common "v1.5" form) -> 1x1 without ReLU, a 1x1 projection on the first
block of each stage, saturating int8 add, ReLU. Stem: 7x7/2 conv, 3x3/2
max-pool (unpadded, see below). Head: global average pool, fully connected to int32 logits.
"""

from reference import Net


def network(h: int = 224, w: int = 224, num_classes: int = 1000,
            width: float = 1.0, blocks=(3, 4, 6, 3)) -> Net:
    net = Net(f"resnet50_{h}x{w}", (h, w, 3))

    def ch(c: int) -> int:
        return max(8, int(c * width))

    y = net.conv("stem", "input", ch(64), 7, stride=2, pad=3)
    # Unpadded, as the program's builder has it: stage 1 runs at 55x55 where
    # Table 1 has 56x56 (pad 1); the configuration lists the departure
    # under `reduced` as `stem_pool_padding`.
    y = net.maxpool("stem.pool", y, 3, 2, pad=0)
    for si, (n, mid) in enumerate(zip(blocks, (64, 128, 256, 512))):
        mid = ch(mid)
        for bi in range(n):
            name, s = f"s{si}.b{bi}", (2 if si > 0 and bi == 0 else 1)
            idn = y
            z = net.conv(f"{name}.c1", y, mid, 1)
            z = net.conv(f"{name}.c2", z, mid, 3, stride=s)
            z = net.conv(f"{name}.c3", z, 4 * mid, 1, relu=False)
            if bi == 0:
                idn = net.conv(f"{name}.ds", y, 4 * mid, 1, stride=s,
                               relu=False)
            y = net.relu(f"{name}.relu", net.add(f"{name}.add", z, idn))
    y = net.gap("gap", y)
    net.fc("fc", y, num_classes)
    return net
