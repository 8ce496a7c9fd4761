"""Seeded class-conditional images, after ``chip_smoke.synthetic_batch``
(the original stays where it is: PERF.md, Open questions): per class a
coarse 4x4 spatial pattern plus a per-channel offset, both fixed by the
seed, under noise. Labels come from the first ``used_classes`` classes, a
signal a few hundred steps can learn, so "the loss falls" is a test.
"""
import numpy as np


def class_protos(rng, used_classes, chw):
    """[used_classes, C, H, W] float32 prototypes from a numpy Generator."""
    c, h, w = chw
    coarse = rng.standard_normal((used_classes, c, 4, 4), dtype=np.float32)
    proto = np.kron(coarse, np.ones((-(-h // 4), -(-w // 4)),
                                    np.float32))[:, :, :h, :w]
    return proto + 2.0 * rng.standard_normal((used_classes, c, 1, 1),
                                             dtype=np.float32)
