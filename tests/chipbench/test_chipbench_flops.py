"""The FLOPs functions against hand counts from the layer tables."""

import pytest

from chipbench_tiny import harness


def test_resnet50_forward_macs_by_hand():
    cell = harness.load_cell("resnet50_b256_x1")
    # He et al. Table 1, 50-layer, v1.5 (stride on the 3x3): per stage the
    # spatial size after the stride, input channels, filters and blocks
    macs = 112 * 112 * 7 * 7 * 3 * 64
    cin = 64
    for size, f, blocks in ((56, 64, 3), (28, 128, 4), (14, 256, 6), (7, 512, 3)):
        for b in range(blocks):
            first = b == 0
            size_in = size * 2 if first and f != 64 else size
            macs += size_in * size_in * cin * f          # 1x1 before the stride
            macs += size * size * 9 * f * f              # 3x3 carries the stride
            macs += size * size * f * 4 * f              # 1x1 expand
            if first:
                macs += size * size * cin * 4 * f        # projection shortcut
            cin = 4 * f
    macs += 2048 * 1000
    assert cell.ref.forward_macs(cell.config) == macs
    assert macs == pytest.approx(4.09e9, rel=0.01)


def test_vit_l16_forward_macs_by_hand():
    cell = harness.load_cell("vit_l16_b32_x1")
    t, d = 197, 1024
    layer = t * (3 * d * d + d * d + 2 * d * 4096) + 2 * t * t * d
    macs = 196 * 768 * d + 24 * layer + d * 1000
    assert cell.ref.forward_macs(cell.config) == macs
    assert macs == pytest.approx(61.6e9, rel=0.01)


@pytest.mark.parametrize("name,params", [("resnet50_b256_x1", 25_557_032),
                                         ("vit_l16_b32_x1", 304_326_632)])
def test_parameter_counts_match_the_published_models(name, params):
    import math

    cell = harness.load_cell(name)
    shapes = cell.ref.param_shapes(cell.config)
    assert sum(math.prod(s) for s, _ in shapes.values()) == params
    assert cell.config["parameters"] == params
