"""A second architecture for the harness: GQA with no q, k or v biases.

Written into a test's root as ``bench/families/gqa_nobias.py``; its
blocks are Qwen2's without the biases, as Llama's are, so it takes the
sizes and FLOPs from Qwen2's family.
"""

import dataclasses
from pathlib import Path

from bench import spec

QWEN2 = spec.family_module("qwen2", Path(__file__).resolve().parents[2])
BIASES = ("bq", "bk", "bv")


def arch_config(config):
    return dataclasses.replace(QWEN2.arch_config(config), qkv_bias=False)


def layer_weights(config):
    return tuple((n, how) for n, how in QWEN2.layer_weights(config)
                 if n not in BIASES)


def program_params(w, cfg):
    from repro.core.bayesian import GaussianVariational
    blocks = {"ln1": w["ln1"], "ln2": w["ln2"],
              "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
              "mlp": {"w1": w["w_gate"], "w3": w["w_up"],
                      "w2": w["w_down"]}}
    return {"embed": {"table": w["embed"]}, "blocks": blocks,
            "final_norm": w["final_norm"],
            "head": {"q": GaussianVariational(mu=w["head_mu"],
                                              rho=w["head_rho"])}}


body_flops_per_token = QWEN2.body_flops_per_token
