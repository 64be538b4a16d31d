"""DeepSeekMoE in the harness: MHA, one dense layer, then MoE layers.

What the harness needs of the architecture (arXiv:2401.06066; the plain
forward is ``bench/references/deepseek_moe.py``) for a configuration
file whose ``"reference"`` is ``"deepseek_moe"``; see
``bench/families/qwen2.py`` for the four functions.  The file holds a
chip's share of the model: ``n_routed_experts`` of each MoE layer's
``router_experts`` experts, from ``expert_offset``.  The router scores
all of them, so its weights are whole.

Weights: attention without biases and norms for every layer; the
first ``first_k_dense_replace`` layers' SwiGLU of ``intermediate_size``;
per MoE layer a float32 router, the held experts' gate and up
projections side by side (``experts_gate_up``: columns [0, f) gate,
[f, 2f) up) and their down projections, and the shared experts'
projections with shared expert s in columns (rows, for the down
projection) [s f, (s + 1) f).  Routers are drawn N(0, 1/d) like every
other weight.

Also here, since only this architecture has them: the work of the
routed expert layer (``expert_work``, read by the ``moe_roofline``
metric) and the MoE layer count (``moe_layers``).
"""

from __future__ import annotations

import dataclasses

from bench import weights as W


def arch_config(config: dict):
    """The program's MoEConfig for a configuration file."""
    from repro.configs.registry import get_config
    if config["scoring_func"] != "softmax" or config["moe_layer_freq"] != 1:
        raise ValueError("the program routes by softmax in every layer "
                         "past the dense ones")
    head = config["bayesian_head"]
    return dataclasses.replace(
        get_config(config["registry"]),
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        num_experts=config["router_experts"],
        top_k=config["num_experts_per_tok"],
        num_shared_experts=config["n_shared_experts"],
        moe_d_ff=config["moe_intermediate_size"],
        first_k_dense=config["first_k_dense_replace"],
        norm_topk=bool(config["norm_topk_prob"]),
        experts_held=config["n_routed_experts"],
        expert_offset=config["expert_offset"],
        param_dtype=config["torch_dtype"],
        mlp_activation=config["hidden_act"],
        norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        qkv_bias=bool(config["attention_bias"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        bayesian_head=True, mc_samples=int(head["mc_samples"]),
        head_init_sigma=float(head["init_sigma"]),
        head_entropy=head["entropy"])


def moe_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def layer_weights(config: dict) -> tuple:
    L, F, Lm = (config["num_hidden_layers"],
                config["first_k_dense_replace"], moe_layers(config))
    d = config["hidden_size"]
    hq = config["num_attention_heads"] * config["head_dim"]
    hkv = config["num_key_value_heads"] * config["head_dim"]
    ff, f = config["intermediate_size"], config["moe_intermediate_size"]
    held, ns = config["n_routed_experts"], config["n_shared_experts"]
    return (("ln1", W.around((L, d), 1.0, W.NORM_GAIN_STD)),
            ("wq", W.normal((L, d, hq), d)),
            ("wk", W.normal((L, d, hkv), d)),
            ("wv", W.normal((L, d, hkv), d)),
            ("wo", W.normal((L, hq, d), hq)),
            ("ln2", W.around((L, d), 1.0, W.NORM_GAIN_STD)),
            ("w_gate", W.normal((F, d, ff), d)),
            ("w_up", W.normal((F, d, ff), d)),
            ("w_down", W.normal((F, ff, d), ff)),
            ("router", W.normal((Lm, d, config["router_experts"]), d,
                                "float32")),
            ("experts_gate_up", W.normal((Lm, held, d, 2 * f), d)),
            ("experts_down", W.normal((Lm, held, f, d), f)),
            ("shared_gate", W.normal((Lm, d, ns * f), d)),
            ("shared_up", W.normal((Lm, d, ns * f), d)),
            ("shared_down", W.normal((Lm, ns * f, d), f)))


def program_params(w: dict, cfg) -> dict:
    from repro.core.bayesian import GaussianVariational
    experts = "experts_ep" if cfg.expert_sharding == "ep" else "experts_tp"
    return {"embed": {"table": w["embed"]},
            "blocks": {"ln1": w["ln1"], "ln2": w["ln2"],
                       "attn": {n: w[n] for n in ("wq", "wk", "wv",
                                                  "wo")}},
            "dense": {"w1": w["w_gate"], "w3": w["w_up"],
                      "w2": w["w_down"]},
            "moe": {"router": {"w": w["router"]},
                    experts: {"w13": w["experts_gate_up"],
                              "w2": w["experts_down"]},
                    "shared": {"w1": w["shared_gate"],
                               "w3": w["shared_up"],
                               "w2": w["shared_down"]}},
            "final_norm": w["final_norm"],
            "head": {"q": GaussianVariational(mu=w["head_mu"],
                                              rho=w["head_rho"])}}


def body_flops_per_token(config: dict) -> float:
    """FLOPs of one token through every block: the attention
    projections, the dense layers' MLP, and per MoE layer the router,
    the shared experts and the routed experts this chip holds.  The
    routed part is an expectation: a token's top_k picks land on this
    chip's share with probability n_routed_experts / router_experts
    each, as near-uniform routing gives (a trained router is skewed)."""
    d = config["hidden_size"]
    hq = config["num_attention_heads"] * config["head_dim"]
    hkv = config["num_key_value_heads"] * config["head_dim"]
    f, E = config["moe_intermediate_size"], config["router_experts"]
    attn = d * hq + 2 * d * hkv + hq * d
    routed = config["num_experts_per_tok"] * config["n_routed_experts"] / E
    moe = d * E + (config["n_shared_experts"] + routed) * 3 * d * f
    params = config["num_hidden_layers"] * attn \
        + config["first_k_dense_replace"] * 3 * d * config[
            "intermediate_size"] + moe_layers(config) * moe
    return 2.0 * params


def expert_work(config: dict, rows: int, experts_hit: int
                ) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the routed experts require for ``rows``
    (token, k) assignments to held experts and ``experts_hit`` held
    experts with at least one (both summed over layers and calls): the
    gate, up and down products per row, 6 d f FLOPs; each hit expert's
    three bf16 d x f matrices read once, and each row's bf16 input read
    and output written."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return 6.0 * d * f * rows, 3.0 * d * f * 2 * experts_hit \
        + 2.0 * d * 2 * rows
