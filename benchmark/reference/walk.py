"""Shape walk of a layer table (``reference/<net>.py``): parameter shapes, and the
operations and bytes one training step needs — the roofline's count.
Nothing here looks at a compiled program, so the count does not move
when the program does.

FLOPs: 2 per multiply-add of every convolution and matmul, three times
over (forward, gradient to the input, gradient to the weights); the
first convolution has no input gradient. Elementwise work is not counted.

Bytes, the least a step must move through HBM: every tensor a
convolution, pooling, residual add or matmul produces is written once
and read once per consumer in the forward pass, its gradient likewise in
the backward pass, and the saved forward tensor is read once more there
(5 passes for a tensor with one consumer), at the activations' width.
BatchNorm and ReLU are taken as fused into the convolution that feeds
them and concatenation as free: a lower bound, so a share of it cannot
pass 100%. Each parameter costs its float32 master copy, gradient and
momentum read and the copy and momentum written (20 bytes).
"""


class _T:
    """A tensor in the walk: its shape and how many layers read it."""

    def __init__(self, shape, parts=None):
        self.shape = tuple(shape)
        self.consumers = 0
        self.parts = parts      # a concatenation reads through to these

    def read(self):
        if self.parts:
            for p in self.parts:
                p.read()
        else:
            self.consumers += 1

    @property
    def size(self):
        n = 1
        for d in self.shape:
            n *= d
        return n


class ShapeOps:
    def __init__(self):
        self.params = {}        # name -> shape, in first-use order
        self.layers = []        # (kind, name, macs, passes, out_shape)
        self.tensors = []
        self._first_conv = True

    def _new(self, shape, parts=None):
        t = _T(shape, parts)
        if not parts:
            self.tensors.append(t)
        return t

    def input(self, shape):
        return _T(shape)        # the batch is fed, not produced: no bytes

    def block(self, fn, x):
        return fn(self, x)

    def conv(self, x, name, cout, k, stride, pad):
        n, c, h, w = x.shape
        ho = (h + 2 * pad - k) // stride + 1
        wo = (w + 2 * pad - k) // stride + 1
        self.params[name + "_weight"] = (cout, c, k, k)
        macs = n * cout * ho * wo * c * k * k
        passes = 2 if self._first_conv else 3
        self._first_conv = False
        x.read()
        self.layers.append(("conv", name, macs, passes, (n, cout, ho, wo)))
        return self._new((n, cout, ho, wo))

    def bn(self, x, name, eps, relu):
        c = x.shape[1]
        self.params[name + "_gamma"] = (c,)
        self.params[name + "_beta"] = (c,)
        return x

    def add_relu(self, a, b):
        a.read()
        b.read()
        return self._new(a.shape)

    def pool(self, x, kind, k, stride, pad):
        n, c, h, w = x.shape
        x.read()
        return self._new((n, c, (h + 2 * pad - k) // stride + 1,
                          (w + 2 * pad - k) // stride + 1))

    def concat(self, xs):
        n, _, h, w = xs[0].shape
        return self._new((n, sum(x.shape[1] for x in xs), h, w), parts=xs)

    def global_avg(self, x):
        x.read()
        return self._new(x.shape[:2])

    def fc(self, x, name, nout):
        n, d = x.shape
        self.params[name + "_weight"] = (nout, d)
        self.params[name + "_bias"] = (nout,)
        x.read()
        self.layers.append(("fc", name, n * d * nout, 3, (n, nout)))
        out = self._new((n, nout))
        out.read()              # the softmax head
        return out

    def flops(self):
        return sum(2 * macs * passes for _, _, macs, passes, _ in self.layers)

    def forward_macs(self):
        return sum(macs for _, _, macs, _, _ in self.layers)

    def bytes(self, act_bytes):
        # per tensor: written once and read once per consumer, forward
        # and again for its gradient, plus one read of the saved copy
        passes = sum(t.size * (2 * (1 + max(t.consumers, 1)) + 1)
                     for t in self.tensors)
        n_param = sum(_prod(s) for s in self.params.values())
        return passes * act_bytes + n_param * 20


def _prod(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def walk(net_fn, input_shape, **net_args):
    """ShapeOps after walking ``net_fn`` over a batch of ``input_shape``."""
    ops = ShapeOps()
    net_fn(ops, ops.input(input_shape), **net_args)
    return ops


def step_cost(net_fn, input_shape, act_bytes, **net_args):
    """(FLOPs, bytes) of one training step on a batch of ``input_shape``."""
    ops = walk(net_fn, input_shape, **net_args)
    return ops.flops(), ops.bytes(act_bytes)
