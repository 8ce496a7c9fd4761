"""Toy-width cells for the CPU tests: the same drivers, generators and
reference at sizes a test run can hold. Not configurations of the
benchmark."""
import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

LIMITS = {"loss_step1_rel_gap": 0.02, "loss_step2_rel_gap": 0.02,
          "loss_step3_rel_gap": 0.02, "step1_excess_noise": 1.0,
          "grad_norm_gap": 0.1, "grad_norm_median_leaf_gap": 0.05,
          "delta_norm_median_leaf_gap": 0.05, "dead_leaves": 0.0,
          "window_loss_over_first_loss": 1.0}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell(kind="resnet", fused=False, compute_dtype="bfloat16"):
    """A cell dict as ``harness.load_cell`` returns it, at toy width. The
    program computes in ``compute_dtype``; the precision the configuration
    states (the noise floor of ``correct``) stays bfloat16."""
    spec = _load(ROOT, "BENCHMARK.json")
    config = copy.deepcopy(_load(BENCH, "configs",
                                 "resnet50_b256_bf16_fused.json"))
    if kind == "resnet":
        config["model"] = {"factory": "mxnet_tpu.models.get_resnet",
                           "args": {"units": [1, 1],
                                    "filter_list": [8, 16, 32],
                                    "num_classes": 10,
                                    "small_input": True}}
        config["reference"] = {"net": "resnet",
                               "args": {"units": [1, 1],
                                        "filters": [8, 16, 32],
                                        "num_classes": 10,
                                        "small_input": True}}
        config["input_chw"] = [3, 16, 16]
    else:
        config["model"] = {"factory": "mxnet_tpu.models.get_inception_bn",
                           "args": {"num_classes": 10}}
        config["reference"] = {"net": "inception_bn",
                               "args": {"num_classes": 10}}
        config["input_chw"] = [3, 64, 64]
    config["batch"] = 8
    config["env"] = {"MXNET_COMPUTE_DTYPE": compute_dtype}
    if fused:
        config["env"]["MXNET_TPU_FUSED_STEP"] = "1"
    return {"spec": spec,
            "cell": {"name": "resnet50_fit_resident", "config": "toy",
                     "traffic": "resident_ring_8", "chips": 1},
            "config": config,
            "traffic": _load(BENCH, "traffic", "resident_ring_8.json"),
            "limits": dict(LIMITS)}
