"""ResNet as a layer table, written once against a small ``ops``
interface and read twice: ``arrays.ArrayOps`` computes it in plain
``jax.numpy`` (the reference), ``walk.ShapeOps`` walks the same table
with shapes only (parameters, FLOPs, bytes: the roofline's count). A
configuration names this file (``"reference": {"net": "resnet"}``) and
the harness calls its ``net``; a new net is a new file beside it.

Written from the paper, not from the program: He et al.,
arXiv:1512.03385, Table 1, 50-layer column, with the bottleneck of Fig. 5
(right) and projection shortcuts (option B) where the shape changes. As
the program builds it (and as the Facebook re-implementation does) every
convolution is followed by BatchNorm, the stride of a down-sampling unit
sits on its 3x3 convolution (the paper puts it on the first 1x1: same
FLOPs for the unit's output, a departure in which tensor is halved
first), eps 2e-5.

Layer names are the program's symbol names, because a checkpoint's
parameter names are the public interface both sides share
(``<conv>_weight``, ``<bn>_gamma`` ...).
"""


def conv_bn_relu(ops, x, prefix, cout, k, stride, pad, eps, relu=True):
    x = ops.conv(x, prefix + "_conv", cout, k, stride, pad)
    return ops.bn(x, prefix + "_bn", eps, relu=relu)


def _bottleneck(ops, x, name, cout, stride, dim_match):
    def body(ops, x):
        y = conv_bn_relu(ops, x, name + "_b1", cout // 4, 1, 1, 0, 2e-5)
        y = conv_bn_relu(ops, y, name + "_b2", cout // 4, 3, stride, 1, 2e-5)
        y = conv_bn_relu(ops, y, name + "_b3", cout, 1, 1, 0, 2e-5,
                         relu=False)
        sc = x if dim_match else conv_bn_relu(
            ops, x, name + "_sc", cout, 1, stride, 0, 2e-5, relu=False)
        return ops.add_relu(y, sc)
    return ops.block(body, x)


def net(ops, x, units=(3, 4, 6, 3), filters=(64, 256, 512, 1024, 2048),
        num_classes=1000, small_input=False):
    if small_input:
        x = conv_bn_relu(ops, x, "stem", filters[0], 3, 1, 1, 2e-5)
    else:
        def stem(ops, x):
            x = conv_bn_relu(ops, x, "stem", filters[0], 7, 2, 3, 2e-5)
            return ops.pool(x, "max", 3, 2, 1)
        x = ops.block(stem, x)
    for stage, (n, cout) in enumerate(zip(units, filters[1:])):
        for unit in range(n):
            stride = 2 if (unit == 0 and stage > 0) else 1
            x = _bottleneck(ops, x, "stage%d_unit%d" % (stage, unit), cout,
                            stride, dim_match=unit > 0)
    x = ops.global_avg(x)
    return ops.fc(x, "fc1", num_classes)
