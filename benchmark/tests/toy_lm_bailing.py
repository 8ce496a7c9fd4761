"""A toy-width cell of the ``fit_lm_ref`` driver over the ``bailing_hybrid``
reference and factory (per-channel delta-rule mixers, latent attention with
values narrower than keys and a head-wise gate, group-limited routed experts
beside a shared one behind a dense layer, the balanced start inside
``init_params``) for the CPU tests. Not a configuration of the benchmark."""
import copy

import toy_lm

CELL = "ling3_flash_fit_packed8k"
ARGS = dict(layer_types=["kda", "kda", "latent_attention", "kda"],
            dense_layers=1, hidden=32, vocab=128, heads=4, kda_key_dim=8,
            kda_value_dim=8, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
            dense_hidden=48, experts_total=32, experts_held=8,
            first_expert=0, top_k=4, n_group=4, topk_group=2,
            expert_hidden=16, swiglu_limits=[[0, 0, 0, 0], [0, 0, 0, 0]],
            seq_len=64, chunk=32, bias_update_rate=0.01)


def cell(compute_dtype="bfloat16", learning_rate=0.003):
    spec = toy_lm._load(toy_lm.ROOT, "BENCHMARK.json")
    config = copy.deepcopy(toy_lm._load(
        toy_lm.BENCH, "configs", "ling3_flash_l6_e8of512_bf16.json"))
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
