"""A toy-width cell of the ``fit_lm_ref`` driver over the ``laguna``
reference and factory (full and sliding-window attention with more query
heads on the windowed layers, a gate a head, scaled rotary on the full
layers, a dense layer 0 and sigmoid-routed experts beside a shared one under a
selection bias the step moves, its balanced start inside ``init_params``)
for the CPU tests. Not a
configuration of the benchmark."""
import copy
import math

import toy_lm

CELL = "laguna_xs2_fit_packed8k"
ARGS = dict(layer_types=["full_attention", "sliding_attention",
                         "full_attention"],
            heads_per_layer=[6, 8, 6], hidden=32, vocab=128, kv_heads=2,
            head_dim=16, window=16, full_rotary_dim=8, yarn_factor=4.0,
            yarn_original_positions=16, yarn_beta_fast=4.0,
            yarn_beta_slow=1.0,
            yarn_attention_factor=0.1 * math.log(4.0) + 1.0, dense_hidden=48,
            experts_total=32, experts_held=8, first_expert=0, top_k=4,
            expert_hidden=16, shared_hidden=16, seq_len=64,
            bias_update_rate=0.01)


def cell(compute_dtype="bfloat16", learning_rate=0.003):
    spec = toy_lm._load(toy_lm.ROOT, "BENCHMARK.json")
    config = copy.deepcopy(toy_lm._load(
        toy_lm.BENCH, "configs", "laguna_xs2_l5_e32of256_bf16.json"))
    config["model"]["args"] = dict(ARGS)
    config["reference"]["args"] = dict(ARGS)
    config["tokens"] = {"batch": 2, "seq_len": ARGS["seq_len"]}
    config["batch"] = 2
    config["check_positions"] = 16
    config["env"] = {"MXNET_COMPUTE_DTYPE": compute_dtype,
                     "MXNET_TPU_FUSED_STEP": "1",
                     "MXNET_BACKWARD_DO_MIRROR": "1"}
    config["fit"]["optimizer_params"]["learning_rate"] = learning_rate
    config["init"]["balance"].update(steps=60, hold=10)
    traffic = toy_lm._load(toy_lm.BENCH, "traffic",
                           "resident_tokens_ring_8.json")
    traffic["params"]["doc_median"] = 12
    return {"spec": spec,
            "cell": {"name": CELL, "config": "toy",
                     "traffic": "resident_tokens_ring_8", "chips": 1},
            "config": config, "traffic": traffic,
            "limits": dict(toy_lm.LIMITS)}
