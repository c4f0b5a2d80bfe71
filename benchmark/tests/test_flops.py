"""The layer-table FLOP count of both models against a hand sum."""

import pytest

from benchmark import flops, manifest as mf

M = mf.load()


def test_vgg11_hand_sum():
    table = mf.load_config(M, "vgg11-cifar-f32")["layer_table"]
    hand = (32 * 32 * 3 * 64 * 9                       # 3->64 at 32x32
            + 16 * 16 * 64 * 128 * 9                   # 64->128 at 16x16
            + 8 * 8 * 128 * 256 * 9 + 8 * 8 * 256 * 256 * 9
            + 4 * 4 * 256 * 512 * 9 + 4 * 4 * 512 * 512 * 9
            + 2 * (2 * 2 * 512 * 512 * 9)
            + 512 * 10)
    assert hand == 152_769_536
    assert flops.forward_macs_per_image(table) == hand
    assert flops.train_flops_per_image(table) == 6 * hand
    assert flops.eval_flops_per_image(table) == 2 * hand


def test_resnet18_hand_sum():
    table = mf.load_config(M, "resnet18-cifar-f32")["layer_table"]
    s1 = 32 * 32 * 3 * 64 * 9 + 4 * (32 * 32 * 64 * 64 * 9)
    s2 = 16 * 16 * (64 * 128 * 9 + 3 * 128 * 128 * 9 + 64 * 128)
    s3 = 8 * 8 * (128 * 256 * 9 + 3 * 256 * 256 * 9 + 128 * 256)
    s4 = 4 * 4 * (256 * 512 * 9 + 3 * 512 * 512 * 9 + 256 * 512)
    hand = s1 + s2 + s3 + s4 + 512 * 10
    assert hand == 555_422_720
    assert flops.forward_macs_per_image(table) == hand


@pytest.mark.parametrize("name", [c["name"] for c in M["configs"]])
def test_layer_table_follows_the_architecture(name):
    """The table is data; the architecture keys beside it say the same."""
    config = mf.load_config(M, name)
    convs = [r for r in config["layer_table"] if r[0] == "conv"]
    if "cfg" in config:
        widths = [c for c in config["cfg"] if c != "M"]
        assert [r[4] for r in convs] == widths
        assert convs[0][3] == 3 and all(r[5] == 3 for r in convs)
    else:
        n3 = 1 + 2 * sum(config["blocks"])
        assert len([r for r in convs if r[5] == 3]) == n3
        assert len([r for r in convs if r[5] == 1]) == 3
    assert config["layer_table"][-1] == ["fc", 1, 1] + config["classifier"] + [1]


def test_unknown_layer_kind_is_refused():
    with pytest.raises(ValueError):
        flops.forward_macs_per_image([["attention", 1, 1, 8, 8, 1]])
