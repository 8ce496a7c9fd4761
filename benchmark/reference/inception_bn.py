"""Inception-BN as a layer table (see ``resnet.py`` for how a table is
read). Written from the paper, not from the program: Ioffe & Szegedy,
arXiv:1502.03167, Fig. 5, in the form of the reference implementation
the repo was modelled on
(example/image-classification/symbol_inception-bn.py): the 5x5 branch is
two 3x3s, pooling is 3x3 (avg inside 3a-5a, max in 5b and in the
stride-2 units), no padding on the two stem pools (floor: 112 -> 55 ->
27, so 3a sees 27x27 where the paper's table says 28x28), eps 1e-3.
Layer names are the program's symbol names.
"""


def _factory(ops, x, name, cout, k, stride=1, pad=0):
    x = ops.conv(x, "conv_" + name, cout, k, stride, pad)
    return ops.bn(x, "bn_" + name, 1e-3, relu=True)


def _inception_a(ops, x, name, n1, n3r, n3, nd3r, nd3, pool, proj):
    def body(ops, x):
        c1 = _factory(ops, x, name + "_1x1", n1, 1)
        c3 = _factory(ops, x, name + "_3x3r", n3r, 1)
        c3 = _factory(ops, c3, name + "_3x3", n3, 3, pad=1)
        cd = _factory(ops, x, name + "_d3x3r", nd3r, 1)
        cd = _factory(ops, cd, name + "_d3x3a", nd3, 3, pad=1)
        cd = _factory(ops, cd, name + "_d3x3b", nd3, 3, pad=1)
        p = ops.pool(x, pool, 3, 1, 1)
        p = _factory(ops, p, name + "_proj", proj, 1)
        return ops.concat([c1, c3, cd, p])
    return ops.block(body, x)


def _inception_b(ops, x, name, n3r, n3, nd3r, nd3):
    def body(ops, x):
        c3 = _factory(ops, x, name + "_3x3r", n3r, 1)
        c3 = _factory(ops, c3, name + "_3x3", n3, 3, stride=2, pad=1)
        cd = _factory(ops, x, name + "_d3x3r", nd3r, 1)
        cd = _factory(ops, cd, name + "_d3x3a", nd3, 3, pad=1)
        cd = _factory(ops, cd, name + "_d3x3b", nd3, 3, stride=2, pad=1)
        p = ops.pool(x, "max", 3, 2, 1)
        return ops.concat([c3, cd, p])
    return ops.block(body, x)


# (kind, name, arguments) rows of arXiv:1502.03167 Fig. 5 from 3a down
_INCEPTION_ROWS = (
    ("a", "3a", 64, 64, 64, 64, 96, "avg", 32),
    ("a", "3b", 64, 64, 96, 64, 96, "avg", 64),
    ("b", "3c", 128, 160, 64, 96),
    ("a", "4a", 224, 64, 96, 96, 128, "avg", 128),
    ("a", "4b", 192, 96, 128, 96, 128, "avg", 128),
    ("a", "4c", 160, 128, 160, 128, 160, "avg", 128),
    ("a", "4d", 96, 128, 192, 160, 192, "avg", 128),
    ("b", "4e", 128, 192, 192, 256),
    ("a", "5a", 352, 192, 320, 160, 224, "avg", 128),
    ("a", "5b", 352, 192, 320, 192, 224, "max", 128),
)


def net(ops, x, num_classes=1000):
    def stem(ops, x):
        x = _factory(ops, x, "1", 64, 7, stride=2, pad=3)
        x = ops.pool(x, "max", 3, 2, 0)
        x = _factory(ops, x, "2r", 64, 1)
        x = _factory(ops, x, "2", 192, 3, pad=1)
        return ops.pool(x, "max", 3, 2, 0)
    x = ops.block(stem, x)
    for kind, name, *args in _INCEPTION_ROWS:
        unit = _inception_a if kind == "a" else _inception_b
        x = unit(ops, x, name, *args)
    x = ops.global_avg(x)
    return ops.fc(x, "fc1", num_classes)
