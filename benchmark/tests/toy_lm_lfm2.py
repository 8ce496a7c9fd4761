"""A toy-width cell of the ``fit_lm_ref`` driver over the ``lfm2_moe``
reference and factory (gated short-convolution mixers, grouped-head
attention at normalised heads, routed experts with no shared one behind a
dense layer, a tied head, the balanced start inside ``init_params``) for
the CPU tests. Not a configuration of the benchmark."""
import copy

import toy_lm

CELL = "lfm2_24b_fit_packed8k"
ARGS = dict(layer_types=["conv", "full_attention", "conv", "conv"],
            dense_layers=1, hidden=32, vocab=128, heads=4, kv_heads=2,
            head_dim=8, dense_hidden=48, experts_total=16, experts_held=4,
            first_expert=0, top_k=3, expert_hidden=16, seq_len=64,
            bias_update_rate=0.01)


def cell(compute_dtype="bfloat16", learning_rate=0.003):
    spec = toy_lm._load(toy_lm.ROOT, "BENCHMARK.json")
    config = copy.deepcopy(toy_lm._load(
        toy_lm.BENCH, "configs", "lfm2_24b_a2b_e8of64_bf16.json"))
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
