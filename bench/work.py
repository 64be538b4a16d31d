"""Work that a kernel's or a step's shapes require: FLOPs and HBM bytes.

Counted from the configuration's shapes alone, never from what the
compiler made of them, so that a change which removes a pad or fuses a
kernel moves the time and not the yardstick.  Weights and the KV cache
are bf16 (2 bytes); the head's mu and sigma are float32 (4 bytes), as
the configuration serves them.
"""

from __future__ import annotations

from pathlib import Path

from bench import spec

BF16 = 2
F32 = 4


def shape(config: dict) -> dict:
    return {"L": config["num_hidden_layers"], "d": config["hidden_size"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "hd": config["head_dim"],
            "V": config["vocab_size"],
            "S": config["bayesian_head"]["mc_samples"]}


def head(config: dict, rows: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one Bayesian-head call over ``rows`` hidden
    states: the mean and variance products ``x @ mu`` and
    ``x**2 @ sigma**2``, S draws of ``mean + std * xi`` per logit; mu and
    sigma read once, x read and five numbers per row written."""
    s = shape(config)
    flops = 4.0 * rows * s["d"] * s["V"] + 2.0 * s["S"] * rows * s["V"]
    byts = 2.0 * s["d"] * s["V"] * F32 + rows * s["d"] * BF16 + rows * 5 * F32
    return flops, byts


def decode_attention(config: dict, depth: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one token's decode attention over ``depth``
    cached positions in every layer: K and V read once."""
    s = shape(config)
    flops = 4.0 * s["H"] * s["hd"] * depth * s["L"]
    byts = 2.0 * s["Hkv"] * s["hd"] * BF16 * depth * s["L"]
    return flops, byts


def body_flops_per_token(config: dict, root: Path = spec.ROOT) -> float:
    """FLOPs of one token through every block, as the configuration's
    family (``bench/families/<reference>.py``) counts them (attention
    over the cache is ``decode_attention``)."""
    return spec.family_module(config["reference"],
                              root).body_flops_per_token(config)


def decode_token_flops(config: dict, depth: int) -> float:
    """FLOPs one served token requires of a decode step: the body, its
    attention at ``depth``, and its row of the head with S samples."""
    return body_flops_per_token(config) + decode_attention(config, depth)[0] \
        + head(config, 1)[0]


def seconds_at_peak(flops: float, byts: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"])
