"""``flops.py`` against counts made by hand."""

import flops
import reference


def test_bottleneck_by_hand():
    # layer1.0 of rn50 at 32x32: 64 -> (1x1) 64 -> (3x3) 64 -> (1x1) 256, plus
    # the 1x1 projection 64 -> 256; all at 32x32, stride 1
    convs = {c["name"]: c for c in reference.conv_list("resnet50", 32)}
    hw = 32 * 32
    by_hand = hw * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    got = sum(flops.conv_macs(convs[f"layer1.0/{n}"])
              for n in ("conv1", "conv2", "conv3", "shortcut/conv"))
    assert got == by_hand == 75_497_472


def test_basic_block_by_hand():
    # layer2.0 of rn18: 3x3 stride 2 64 -> 128 (32x32 -> 16x16), 3x3 128 -> 128,
    # and the 1x1 stride-2 projection 64 -> 128
    convs = {c["name"]: c for c in reference.conv_list("resnet18", 32)}
    hw = 16 * 16
    by_hand = hw * (9 * 64 * 128 + 9 * 128 * 128 + 64 * 128)
    got = sum(flops.conv_macs(convs[f"layer2.0/{n}"])
              for n in ("conv1", "conv2", "shortcut/conv"))
    assert got == by_hand == 58_720_256


def test_known_totals():
    assert abs(flops.forward_macs_per_view("resnet50", 32) / 1e9 - 1.298) < 0.005
    assert abs(flops.forward_macs_per_view("resnet18", 32) / 1e9 - 0.555) < 0.005


def test_step_flops_by_parts():
    rows = 512
    conv = flops.conv_flops_per_step("resnet50", 32, rows)
    fwd = 2 * flops.forward_macs_per_view("resnet50", 32) * rows
    stem = 2 * 32 * 32 * 27 * 64 * rows
    assert conv == 3 * fwd - stem  # the stem has no gradient to its input
    total = flops.step_flops("resnet50", 32, 256)
    dense = 3 * 2 * (2048 * 2048 + 2048 * 128) * rows
    loss = 3 * 2 * rows * rows * 128
    assert total == conv + dense + loss
    assert flops.flops_per_image("resnet50", 32, 256) == total / 256


def test_roofline_side_and_bytes():
    least, side = flops.conv_min_seconds("resnet50", 32, 512, 197e12, 819e9)
    assert side == "bytes"
    assert least == flops.conv_min_bytes_per_step("resnet50", 32, 512) / 819e9
    # one conv by hand: the stem at 1 row reads 32*32*3 + 27*64, writes 32*32*64,
    # forward and weight gradient only
    one = 4 * 2 * (32 * 32 * 3 + 27 * 64 + 32 * 32 * 64)
    rest = sum(1 for c in reference.conv_list("resnet18", 32)) - 1
    assert rest == 19
    stem_only = flops.conv_min_bytes_per_step("resnet18", 32, 1) - sum(
        4 * 3 * (c["hin"] ** 2 * c["cin"] + c["hout"] ** 2 * c["cout"] + c["k"] ** 2 * c["cin"] * c["cout"])
        for c in reference.conv_list("resnet18", 32)[1:])
    assert stem_only == one
