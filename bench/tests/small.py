"""Small cells for the CPU tests: the registry's models at reduced widths."""
import copy

SMALL_MODELS = {
    "qwen2-0.5b": {
        "overrides": {"n_layers": 2, "d_model": 64, "d_ff": 128, "vocab_size": 256,
                      "attention": {"n_heads": 4, "n_kv_heads": 2, "head_dim": 16}},
        "model": {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
                  "norm": "rmsnorm", "norm_eps": 1e-6, "act": "swiglu",
                  "qkv_bias": True, "rope_theta": 1000000.0,
                  "tie_embeddings": True},
    },
    "olmo-1b": {
        "overrides": {"n_layers": 2, "d_model": 64, "d_ff": 128, "vocab_size": 256,
                      "tie_embeddings": True,
                      "attention": {"n_heads": 4, "n_kv_heads": 4, "head_dim": 16}},
        "model": {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 4, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
                  "norm": "nonparametric_ln", "norm_eps": 1e-5, "act": "swiglu",
                  "qkv_bias": False, "rope_theta": 10000.0,
                  "tie_embeddings": True},
    },
}

TRAIN = {"kind": "train", "batch": 4, "seq_len": 32, "precision": "paper_sr_bf16",
         "kernel_backend": "pallas", "remat": "block",
         "optimizer": {"name": "adamw", "lr": 3e-4, "weight_decay": 0.1,
                       "b1": 0.9, "b2": 0.95, "eps": 1e-8},
         "check_steps": 3}

# limits of the small cells, set from small-size readings on the CPU:
# program loss gap <= 1e-4, grad gap <= 2.8e-3, grad diff 0.0116-0.0137,
# change gap 0.29-0.37 over 2-4 seeds each of both models; the float8
# control's grad diff 0.16-0.24, half of the batch 0.87-1.0; a state left
# unchanged reads a change gap of 1
TRAIN_LIMITS = {"loss_gap": 2.5e-4, "grad_diff": 0.05, "change_gap": 0.6}
SERVE_LIMITS = {"logit_gap": 0.01}

SERVE = {"kind": "serve", "slots": 4, "max_len": 96, "prefill_chunk": 16,
         "kernel_backend": "pallas", "rate_per_s": 4.0, "prompt_len": [8, 48],
         "output_len": [4, 24], "warmup_s": 1, "check": {"min_requests": 3,
                                                          "min_tokens": 40,
                                                          "max_requests": 4}}


def config(arch: str) -> dict:
    c = copy.deepcopy(SMALL_MODELS[arch])
    c["arch"] = arch
    return c


def files(arch: str, traffic: dict, limits: dict, chips: int = 1) -> tuple:
    name = f"{arch}.small"
    cell = {"name": name, "config": arch, "traffic": "small", "chips": chips}
    return cell, config(arch), copy.deepcopy(traffic), {"limits": dict(limits)}


def bench(name: str, kind: str) -> dict:
    """BENCHMARK.json with the small cell `name` added to every metric of
    the cells of its kind ("train" or "serve")."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    for group in ("end_to_end", "per_layer"):
        for e in b[group]:
            if any(w.endswith("." + kind) for w in e.get("workloads", [])):
                e["workloads"] = e["workloads"] + [name]
    return b
