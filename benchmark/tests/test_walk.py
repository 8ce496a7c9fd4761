"""The shape walk's FLOPs and bytes against hand-worked layer values."""
import pytest

from benchmark.reference import inception_bn, resnet, train, walk

B = 256
SHAPE = (B, 3, 224, 224)


def _layer(ops, name):
    return next(l for l in ops.layers if l[1] == name)


def test_resnet50_layers_and_totals():
    ops = walk.walk(resnet.net, SHAPE)
    # stem: 64 filters of 7x7x3 at 112x112
    assert _layer(ops, "stem_conv")[2] == B * 64 * 112 * 112 * 3 * 49
    assert _layer(ops, "stem_conv")[3] == 2      # no gradient to the image
    # conv2_x first 1x1: 64 <- 64 at 56x56; its 3x3: 64 <- 64 x9
    assert _layer(ops, "stage0_unit0_b1_conv")[2] == B * 64 * 56 * 56 * 64
    assert _layer(ops, "stage0_unit0_b2_conv")[2] == \
        B * 64 * 56 * 56 * 64 * 9
    # conv5_x projection shortcut: 2048 <- 1024, stride 2 -> 7x7
    assert _layer(ops, "stage3_unit0_sc_conv")[2] == \
        B * 2048 * 7 * 7 * 1024
    assert _layer(ops, "fc1")[2] == B * 2048 * 1000
    # He et al. Table 1: 3.8e9 multiply-adds (4.09e9 with the projection
    # shortcuts and the stride on the 3x3); 25.6 M parameters
    assert ops.forward_macs() / B == 4089184256
    assert sum(walk._prod(s) for s in ops.params.values()) == 25557032
    stem = 2 * B * 64 * 112 * 112 * 3 * 49
    assert ops.flops() == 6 * ops.forward_macs() - stem


def test_inception_bn_layers_and_totals():
    ops = walk.walk(inception_bn.net, SHAPE)
    assert _layer(ops, "conv_1")[4] == (B, 64, 112, 112)
    # unpadded stem pools: 112 -> 55 -> 27
    assert _layer(ops, "conv_2")[2] == B * 192 * 55 * 55 * 64 * 9
    assert _layer(ops, "conv_3a_1x1")[4] == (B, 64, 27, 27)
    # 3c halves to 14x14, 4e to 7x7
    assert _layer(ops, "conv_4a_1x1")[4] == (B, 224, 14, 14)
    assert _layer(ops, "conv_5b_proj")[4] == (B, 128, 7, 7)
    assert _layer(ops, "fc1")[2] == B * 1024 * 1000
    assert ops.forward_macs() / B == 1987204096
    assert sum(walk._prod(s) for s in ops.params.values()) == 11285224


def test_bytes_of_a_two_layer_net_by_hand():
    """conv -> conv -> global pool -> fc on a 1x1x4x4 input: every
    produced tensor costs 5 passes (one consumer), a parameter 20 bytes."""
    def net(ops, x):
        x = ops.conv(x, "a", 2, 3, 1, 1)      # (1, 2, 4, 4): 32 elements
        x = ops.conv(x, "b", 4, 1, 1, 0)      # (1, 4, 4, 4): 64
        x = ops.global_avg(x)                 # (1, 4): 4
        return ops.fc(x, "fc", 3)             # (1, 3): 3

    ops = walk.walk(net, (1, 1, 4, 4))
    params = 2 * 1 * 9 + 4 * 2 + 3 * 4 + 3
    assert ops.bytes(2) == (32 + 64 + 4 + 3) * 5 * 2 + params * 20
    macs = 2 * 16 * 9 + 4 * 16 * 2 + 12
    assert ops.forward_macs() == macs
    assert ops.flops() == 2 * (2 * 2 * 16 * 9 + 3 * (4 * 16 * 2 + 12))


def test_concat_reads_through_to_its_parts():
    def net(ops, x):
        a = ops.conv(x, "a", 2, 1, 1, 0)
        b = ops.conv(x, "b", 2, 1, 1, 0)
        y = ops.concat([a, b])
        c = ops.conv(y, "c", 2, 1, 1, 0)
        d = ops.pool(y, "max", 1, 1, 0)
        return ops.fc(ops.global_avg(ops.concat([c, d])), "fc", 2)

    ops = walk.walk(net, (1, 1, 2, 2))
    a = ops.tensors[0]
    assert a.consumers == 2          # read by c's conv and by the pool
    assert len(ops.tensors) == 6     # a, b, c, d, pooled, logits: no concat


@pytest.mark.parametrize("net,want_ms", [("resnet", (31.6, 60.3)),
                                         ("inception_bn", (15.2, 20.0))])
def test_v5e_least_times(net, want_ms):
    """The roofline's two times on the v5e's peaks, to a tenth of a ms:
    bytes bind for both nets at batch 256."""
    flops, nbytes = walk.step_cost(train.load_net(net), SHAPE, 2)
    assert round(flops / 197e12 * 1e3, 1) == want_ms[0]
    assert round(nbytes / 819e9 * 1e3, 1) == want_ms[1]
