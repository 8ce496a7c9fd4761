"""Which body each language cell's kernel-bearing nodes lower to, at the
cell's own sizes: the model is built from ``benchmark/configs/<name>.json``
(its ``model`` block: the published widths; its ``tokens`` block: one
packed sequence of 8,192 positions) and traced abstractly, bfloat16 over
the arguments an operator does not keep whole (``jax.eval_shape`` over
``executor.make_graph_eval`` under recomputation: nothing is compiled,
nothing allocated). Every operator that chooses a body from its shapes
counts the choice once a traced node (``lower.*``), so the counters read
the Pallas body for every scan, delta-rule, attention and experts node and
nothing for any XLA fallback (an attention node also counts its two
passes: ``attention_forward.fused``, this repo's forward kernel, or
``.splash``, JAX's; ``attention_backward.fused``, this repo's one kernel of
five products, or ``.split``, JAX's two of seven; and its mask, ``attention_mask.causal``
or ``.window``, a windowed node the pairs of blocks its band holds:
``attention_window.block_pairs``); a delta-rule node also counts WHERE its
kernels read the op's wide arrays (``delta_rule_layout.rows``: as the
projections leave them, at heads of whole lane tiles; ``.heads``: float32
head-major copies, the Olmo cell's 96 x 192). The toy presets of the
models' own test files count the fallbacks: a change that drops a cell off
its kernel passes them all, and costs 10-50% of the cell's step on the chip
(ledger, PRs 27, 31, 33, 37, 41). Only the configuration files are read;
nothing of the benchmark is imported."""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.executor import make_graph_eval

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
# the counter a node of each operator must count at a cell's sizes, and
# the ones it must not (``GatedShortConv`` has one body, XLA's)
KERNEL = {
    "SSMScan": ["scan_kernel.pallas_chunked"],
    "GatedDeltaRule": ["delta_rule_kernel.pallas_chunked"],
    "CausalAttention": ["attention_kernel.pallas_splash",
                        "attention_layout.fused",
                        "attention_forward.fused",
                        "attention_backward.fused"],
    "RoutedExperts": ["experts_kernel.pallas_grouped",
                      "experts_plan.column_sort"],
    "GatedShortConv": ["shortconv_body.xla_fused"],
}
# the entry each cell's ``GatedDeltaRule`` nodes take, all of them
DELTA_LAYOUT = {
    "olmo_hybrid_l4_headshare_bf16": "heads",
    "ling3_flash_l6_e8of512_bf16": "rows",
    "qwen3_next_l4_e32of512_bf16": "rows",
}
FALLBACKS = ["scan_kernel.xla_chunked", "delta_rule_kernel.xla_chunked",
             "attention_kernel.xla_blockwise", "attention_layout.split",
             "attention_forward.splash", "attention_backward.split",
             "experts_kernel.xla_loop"]
# the operators each cell's cut of its model holds, by node
CELLS = {
    "nemotron3_nano_l9_e8of128_bf16": {
        "SSMScan": 4, "RoutedExperts": 4, "CausalAttention": 1},
    "olmo_hybrid_l4_headshare_bf16": {
        "GatedDeltaRule": 3, "CausalAttention": 1},
    "glm47_flash_l6_e8of64_bf16": {"CausalAttention": 6, "RoutedExperts": 5},
    "lfm2_24b_a2b_e8of64_bf16": {
        "GatedShortConv": 7, "CausalAttention": 2, "RoutedExperts": 8},
    "ling3_flash_l6_e8of512_bf16": {
        "GatedDeltaRule": 5, "RoutedExperts": 5, "CausalAttention": 1},
    "qwen3_next_l4_e32of512_bf16": {
        "GatedDeltaRule": 3, "RoutedExperts": 4, "CausalAttention": 1},
    "laguna_xs2_l5_e32of256_bf16": {"CausalAttention": 5, "RoutedExperts": 4},
}
# the cells whose attentions are not all causal: (windowed nodes, the pairs
# of 512-blocks the band holds a node, of the causal half's 136)
WINDOWED = {"laguna_xs2_l5_e32of256_bf16": (3, 31)}


def abstract_arguments(net, shape):
    """The arguments and auxiliary states of ``net`` over ``shape`` int32
    ids and labels, as shapes and types alone, cast as ``Executor`` casts
    under a bfloat16 compute dtype: every float argument but the labels and
    what an operator declares it reads whole (``full_precision_args``)."""
    arg_shapes, _, aux_shapes = net.infer_shape(data=shape,
                                                softmax_label=shape)
    arg_types, _, aux_types = net.infer_type()
    whole = {"data", "softmax_label"}
    for node in net._topo():
        if node.is_variable:
            continue
        keep = getattr(node.op, "full_precision_args", ())
        whole.update(src.name for slot, (src, _)
                     in zip(node.op.list_arguments(), node.inputs)
                     if slot in keep and src.is_variable)
    args = [jax.ShapeDtypeStruct(
        s, np.int32 if n in ("data", "softmax_label")
        else t if n in whole else jnp.bfloat16)
        for n, s, t in zip(net.list_arguments(), arg_shapes, arg_types)]
    return args, [jax.ShapeDtypeStruct(s, t)
                  for s, t in zip(aux_shapes, aux_types)]


@pytest.mark.parametrize("config", sorted(CELLS))
def test_a_cells_nodes_take_their_kernels_at_its_own_sizes(config):
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        cell = json.load(f)
    module, _, factory = cell["model"]["factory"].rpartition(".")
    net = getattr(importlib.import_module(module), factory)(
        **cell["model"]["args"])
    assert cell["tokens"]["seq_len"] == 8192
    assert cell["env"]["MXNET_COMPUTE_DTYPE"] == "bfloat16"
    held = {}
    for node in json.loads(net.tojson())["nodes"]:
        if node["op"] in KERNEL:
            held[node["op"]] = held.get(node["op"], 0) + 1
    assert held == CELLS[config]
    args, aux = abstract_arguments(
        net, (cell["tokens"]["batch"], cell["tokens"]["seq_len"]))
    eval_graph, _ = make_graph_eval(
        net, remat=cell["env"]["MXNET_BACKWARD_DO_MIRROR"] == "1")
    telemetry.reset()
    telemetry.enable()
    try:
        outs, _ = jax.eval_shape(
            lambda a, x: eval_graph(a, x, jax.random.PRNGKey(0), True),
            args, aux)
        counted = {name: telemetry.peek("lower." + name) or 0
                   for names in KERNEL.values() for name in names}
        fallen = {name: telemetry.peek("lower." + name) or 0
                  for name in FALLBACKS}
        layout = {name: telemetry.peek("lower.delta_rule_layout." + name)
                  or 0 for name in ("rows", "heads")}
        mask = {name: telemetry.peek("lower." + name) or 0 for name in (
            "attention_mask.causal", "attention_mask.window",
            "attention_window.block_pairs",
            "attention_window.block_pairs_causal")}
    finally:
        telemetry.reset()
        telemetry.disable()
    assert outs[0].shape[0] in (8192 * cell["tokens"]["batch"],
                                cell["tokens"]["batch"])
    assert counted == {name: held.get(op, 0)
                       for op, names in KERNEL.items() for name in names}
    assert fallen == dict.fromkeys(FALLBACKS, 0)
    assert layout == dict({"rows": 0, "heads": 0}, **{
        DELTA_LAYOUT[config]: held["GatedDeltaRule"]}
        if config in DELTA_LAYOUT else {})
    windowed, pairs = WINDOWED.get(config, (0, 0))
    assert mask == {
        "attention_mask.causal": held.get("CausalAttention", 0) - windowed,
        "attention_mask.window": windowed,
        "attention_window.block_pairs": windowed * pairs,
        "attention_window.block_pairs_causal": windowed * 136}
