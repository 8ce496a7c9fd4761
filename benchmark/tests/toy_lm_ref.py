"""A toy-width cell of the ``fit_lm_ref`` driver for the CPU tests: the
``olmo_hybrid`` reference and factory and the ``resident_tokens`` generator
at sizes a test run can hold. Not a configuration of the benchmark."""
import copy

import toy_lm

CELL = "olmo_hybrid_fit_packed8k"
ARGS = dict(layer_types=["linear_attention", "linear_attention",
                         "linear_attention", "full_attention"],
            hidden=32, vocab=128, heads=4, heads_held=2, first_head=0,
            head_dim=8, linear_key_dim=6, linear_value_dim=12,
            ffn_hidden=48, seq_len=64, chunk=16)


def cell(compute_dtype="bfloat16", learning_rate=0.003):
    spec = toy_lm._load(toy_lm.ROOT, "BENCHMARK.json")
    config = copy.deepcopy(toy_lm._load(
        toy_lm.BENCH, "configs", "olmo_hybrid_l4_headshare_bf16.json"))
    config["model"]["args"] = dict(ARGS)
    config["reference"]["args"] = dict(ARGS)
    config["tokens"] = {"batch": 2, "seq_len": ARGS["seq_len"]}
    config["batch"] = 2
    config["check_positions"] = 16
    config["env"] = {"MXNET_COMPUTE_DTYPE": compute_dtype,
                     "MXNET_TPU_FUSED_STEP": "1",
                     "MXNET_BACKWARD_DO_MIRROR": "1"}
    config["fit"]["optimizer_params"]["learning_rate"] = learning_rate
    traffic = toy_lm._load(toy_lm.BENCH, "traffic",
                           "resident_tokens_ring_8.json")
    traffic["params"]["doc_median"] = 12
    return {"spec": spec,
            "cell": {"name": CELL, "config": "toy",
                     "traffic": "resident_tokens_ring_8", "chips": 1},
            "config": config, "traffic": traffic,
            "limits": dict(toy_lm.LIMITS)}
