"""A toy-width language-model cell for the CPU tests: the ``fit_lm``
driver, the ``resident_tokens`` generator and the ``nemotron_h`` reference
at sizes a test run can hold. Not a configuration of the benchmark."""
import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

ARGS = dict(pattern="MEMEMEM*E", hidden=32, vocab=128, experts_total=16,
            experts_held=4, first_expert=0, seq_len=64, mamba_heads=4,
            mamba_head_dim=8, ssm_groups=2, ssm_state=8, chunk=16,
            attn_heads=4, kv_heads=2, head_dim=8, top_k=3, expert_hidden=16,
            shared_hidden=24, bias_update_rate=0.01)

LIMITS = {"loss_step1_rel_gap": 0.01, "loss_step2_rel_gap": 0.01,
          "loss_step3_rel_gap": 0.01, "step1_excess_noise": 1.0,
          "grad_norm_gap": 0.05, "grad_norm_median_leaf_gap": 0.03,
          "delta_norm_median_leaf_gap": 0.03, "dead_leaves": 0.0,
          "window_loss_over_first_loss": 1.0}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell(compute_dtype="bfloat16", learning_rate=0.003):
    spec = _load(ROOT, "BENCHMARK.json")
    config = copy.deepcopy(_load(
        BENCH, "configs", "nemotron3_nano_l9_e8of128_bf16.json"))
    config["model"]["args"] = dict(ARGS)
    config["reference"]["args"] = dict(ARGS)
    config["tokens"] = {"batch": 2, "seq_len": ARGS["seq_len"]}
    config["batch"] = 2
    config["check_positions"] = 16
    config["env"] = {"MXNET_COMPUTE_DTYPE": compute_dtype,
                     "MXNET_TPU_FUSED_STEP": "1",
                     "MXNET_BACKWARD_DO_MIRROR": "1"}
    config["fit"]["optimizer_params"]["learning_rate"] = learning_rate
    traffic = _load(BENCH, "traffic", "resident_tokens_ring_8.json")
    traffic["params"]["doc_median"] = 12
    return {"spec": spec,
            "cell": {"name": "nemotron3_nano_fit_packed8k", "config": "toy",
                     "traffic": "resident_tokens_ring_8", "chips": 1},
            "config": config, "traffic": traffic, "limits": dict(LIMITS)}
