"""Qwen2 in the harness: dense GQA with biases on q, k and v, SwiGLU.

What the harness needs of an architecture, for a configuration file
whose ``"reference"`` is ``"qwen2"`` (the published architecture,
arXiv:2407.10671; the plain forward is ``bench/references/qwen2.py``):

* ``arch_config(config)``: the program's ``ArchConfig``;
* ``layer_weights(config)``: how ``bench.weights`` draws each per-layer
  weight, stacked over the layers, in the order of their keys;
* ``program_params(w, cfg)``: those weights in the program's parameter
  tree (``bench.serve.program`` checks it against the program's own);
* ``body_flops_per_token(config)``: FLOPs of one token through every
  block's projections and MLP (``bench.work`` adds attention and head).
"""

from __future__ import annotations

import dataclasses

from bench import weights as W

# q, k and v biases are drawn around 0 with this spread, so that a path
# which drops them shows
QKV_BIAS_STD = 0.1


def arch_config(config: dict):
    """The program's ArchConfig for a configuration file: the registry
    entry it names gives the family, every size comes from the file."""
    from repro.configs.registry import get_config
    head = config["bayesian_head"]
    return dataclasses.replace(
        get_config(config["registry"]),
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        param_dtype=config["torch_dtype"],
        mlp_activation=config["hidden_act"],
        norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]), qkv_bias=True,
        tie_embeddings=bool(config["tie_word_embeddings"]),
        bayesian_head=True, mc_samples=int(head["mc_samples"]),
        head_init_sigma=float(head["init_sigma"]),
        head_entropy=head["entropy"])


def layer_weights(config: dict) -> tuple:
    L, d = config["num_hidden_layers"], config["hidden_size"]
    hq = config["num_attention_heads"] * config["head_dim"]
    hkv = config["num_key_value_heads"] * config["head_dim"]
    ff = config["intermediate_size"]
    return (("ln1", W.around((L, d), 1.0, W.NORM_GAIN_STD)),
            ("wq", W.normal((L, d, hq), d)),
            ("bq", W.around((L, hq), 0.0, QKV_BIAS_STD)),
            ("wk", W.normal((L, d, hkv), d)),
            ("bk", W.around((L, hkv), 0.0, QKV_BIAS_STD)),
            ("wv", W.normal((L, d, hkv), d)),
            ("bv", W.around((L, hkv), 0.0, QKV_BIAS_STD)),
            ("wo", W.normal((L, hq, d), hq)),
            ("ln2", W.around((L, d), 1.0, W.NORM_GAIN_STD)),
            ("w_gate", W.normal((L, d, ff), d)),
            ("w_up", W.normal((L, d, ff), d)),
            ("w_down", W.normal((L, ff, d), ff)))


def program_params(w: dict, cfg) -> dict:
    from repro.core.bayesian import GaussianVariational
    blocks = {"ln1": w["ln1"], "ln2": w["ln2"],
              "attn": {n: w[n] for n in ("wq", "bq", "wk", "bk", "wv",
                                         "bv", "wo")},
              "mlp": {"w1": w["w_gate"], "w3": w["w_up"],
                      "w2": w["w_down"]}}
    return {"embed": {"table": w["embed"]}, "blocks": blocks,
            "final_norm": w["final_norm"],
            "head": {"q": GaussianVariational(mu=w["head_mu"],
                                              rho=w["head_rho"])}}


def body_flops_per_token(config: dict) -> float:
    d = config["hidden_size"]
    hq = config["num_attention_heads"] * config["head_dim"]
    hkv = config["num_key_value_heads"] * config["head_dim"]
    params = d * hq + 2 * d * hkv + hq * d + 3 * d * config[
        "intermediate_size"]
    return 2.0 * params * config["num_hidden_layers"]
