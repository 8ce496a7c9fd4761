"""A toy-width cell of the ``fit_lm_ref`` driver over the ``qwen3_next``
reference and factory (delta-rule mixers with two value heads a key head,
attention with an output gate and part-rotary normed heads, softmax-routed
experts with the auxiliary loss beside a gated shared expert in every layer,
the routers' balanced start inside ``init_params``) for the CPU tests. Not a
configuration of the benchmark."""
import copy

import toy_lm

CELL = "qwen3_next_fit_packed8k"
ARGS = dict(layer_types=["linear_attention", "linear_attention",
                         "full_attention", "linear_attention"],
            hidden=32, vocab=128, heads=4, kv_heads=2, head_dim=8,
            rotary_dim=4, linear_key_heads=2, linear_value_heads=4,
            linear_key_dim=6, linear_value_dim=10, experts_total=32,
            experts_held=8, first_expert=0, top_k=4, expert_hidden=16,
            shared_hidden=16, aux_loss_coef=0.001, seq_len=64, chunk=32)


def cell(compute_dtype="bfloat16", learning_rate=0.003):
    spec = toy_lm._load(toy_lm.ROOT, "BENCHMARK.json")
    config = copy.deepcopy(toy_lm._load(
        toy_lm.BENCH, "configs", "qwen3_next_l4_e32of512_bf16.json"))
    config["model"]["args"] = dict(ARGS)
    config["reference"]["args"] = dict(ARGS)
    config["tokens"] = {"batch": 2, "seq_len": ARGS["seq_len"]}
    config["batch"] = 2
    config["check_positions"] = 16
    config["env"] = {"MXNET_COMPUTE_DTYPE": compute_dtype,
                     "MXNET_TPU_FUSED_STEP": "1",
                     "MXNET_BACKWARD_DO_MIRROR": "1"}
    config["fit"]["optimizer_params"]["learning_rate"] = learning_rate
    config["init"]["balance"].update({"from": 0.3, "to": 0.01, "steps": 60,
                                    "hold": 10})
    traffic = toy_lm._load(toy_lm.BENCH, "traffic",
                           "resident_tokens_ring_8.json")
    traffic["params"]["doc_median"] = 12
    return {"spec": spec,
            "cell": {"name": CELL, "config": "toy",
                     "traffic": "resident_tokens_ring_8", "chips": 1},
            "config": config, "traffic": traffic,
            "limits": dict(toy_lm.LIMITS)}
