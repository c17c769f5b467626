"""The FLOP functions against hand-counted layers."""

from conftest import load_cfg

from references import nnref, vgg


def test_one_convolution_and_one_dense_layer():
    # 32x32 outputs, 3x3 window, 3 -> 64 channels: 1024*9*3*64 MACs.
    assert nnref.conv_flops(32, 32, 3, 3, 3, 64) == 2 * 1024 * 9 * 3 * 64 == 3_538_944
    assert nnref.dense_flops(512, 10) == 10_240


def test_vgg16_at_cifar_size_by_hand():
    # (side, cin, cout) of the 13 convolutions of configuration D on 32x32.
    convs = [(32, 3, 64), (32, 64, 64), (16, 64, 128), (16, 128, 128),
             (8, 128, 256), (8, 256, 256), (8, 256, 256), (4, 256, 512),
             (4, 512, 512), (4, 512, 512), (2, 512, 512), (2, 512, 512),
             (2, 512, 512)]
    want = sum(2 * s * s * 9 * i * o for s, i, o in convs) + 2 * 512 * 512 + 2 * 512 * 10
    assert want == 626_927_616
    assert vgg.forward_flops(load_cfg("vgg16_cifar")) == want
