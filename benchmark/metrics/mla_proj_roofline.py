"""Kernels: latent attention's projection chains' share of their roofline
(``scopes.part_roofline``; the count is the configuration's reference's,
``reference/glm4_moe_lite.py: layer_cost``: 2 x 21.76 M x tokens a pass,
each weight, the two latents, q, k, val and the output once)."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_roofline(trace, cell, "attention_proj")
