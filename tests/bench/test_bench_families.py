"""What moved into ``bench/families/``, against the parent's numbers.

The weights and the ``ArchConfig`` that each configuration's family
gives are those the harness gave before the architecture left its shared
modules: the digests and fields below were taken from the harness
before that move (weights on the CPU at ``data/tiny.json``'s size).  A
digest covers an array's dtype, shape and bytes.
"""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

from _paths import DATA, ROOT
from bench import spec, weights

TINY = json.loads((DATA / "tiny.json").read_text())
QWEN2_1_5B = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())

DIGESTS = {
    7: {
        "bk": "972662dd1e50fa4616d9dacee959ae0c57eca4d57f38398a3594211acbd49120",
        "bq": "405b9758c96afd457410b9e58267c6500126ce9efb2801c92f93c2bb3a63d87c",
        "bv": "0292a6964e9557e8cf15d075a96cb79d08e205dd8b5e508ccf43ea5374a5bac6",
        "embed": "7ed6c25690180121b12fa715075513cd6c7050c93e60931194d45bfc84105f78",
        "final_norm": "5e9b66cfeec53ef3edcdc5375791c21db3de3e0660eda7b9adb8075ef5756321",
        "head_mu": "cec04de573fad6fd5117fb6be3777c596bc61117ae177686ecbd208d7f477ffb",
        "head_rho": "4f4cdfeb6abe20e76ec5c0c155accc670d9f6a183186b2470a8f29e4232ae68b",
        "ln1": "f82e4f0a1d4fe7f12affa0b703fd8f1b361189e7a498e7a0ed9f36268d699bf6",
        "ln2": "2d160176d177a187106441c4af46ec8187dca29f92969a819128a6182e16865d",
        "w_down": "b44f961069b779dc0f52653cc24fc89af3f2571d122af87ae4f2cab68a360c2d",
        "w_gate": "5754baefba3ef13b57ddf1073e1cb73c6f13e7928dac26f4173aa60b82193bd5",
        "w_up": "99bfdaebb52bfdf06069613c71713f3ba3511733a3e8996a3bd1a69fdaf671b5",
        "wk": "85e65f5fe3e6b333037e61551d220cb3cd9d7e4e8fe6cbe23640e70da555006d",
        "wo": "d1b50c6fdc56d1b7a18d768c4509a3c856d5ba5d46a671ec20a838b92e442b64",
        "wq": "0c35e3eb758486ea1736a7b3b257cefdd29013492b0fa955c04ab6047477dca5",
        "wv": "7014e70e0df7cef69c3381acec9629f431f0d0688c85a0cfcbdcf73c4d39034d",
    },
    2147483653: {
        "bk": "3e0008fe8457203a6f5710279038d3716ed37934ab2016f9bb81fd283229f9dc",
        "bq": "a38f66f2692bae30273b6a841e5b6a5c18b3712eb072263bd479a2d13f8e1593",
        "bv": "7cb4ea32570dbd499701511c153f6e71468d82e05fbfb6b739f7f1db9d433878",
        "embed": "ae4f8555d1b60e4cdcadac12647e07444b4b9d2bfeb53d5c7a7a9bcf644198aa",
        "final_norm": "831b54d8cae0141e13b6c920ce2f8945d58391bf056d4e220ba8cf0cf83b0f1d",
        "head_mu": "52ccc785f8fce1ad4d901340848cc4cb6f0aa5bc013e022c54b0d7548f99f1d1",
        "head_rho": "3bd0f3b08b070bed815afbb7648e7e53f93435b3f6c05dbda1b041698b0eb322",
        "ln1": "818d2dc16986050d18f58c4dd0311930dc3d3c6b16ce408c002e033ddce7d44e",
        "ln2": "b9b4c70dd05cdf12d7dfc14739a0f85c5cc857ecf3cf5e244b7c08345fb17dc4",
        "w_down": "0a3db0a935284a40a49eaba569a688c67906832847864b4f9d8f7acd18c02b31",
        "w_gate": "c90dcd475b6ae09da4a00e7c9137a4171e5fbe5b09743edccce90cf8e35aca25",
        "w_up": "61e6a7d3dcbb9a348f9c8c8bd3acce73986e24b90adaef2333b4d14df1bb5458",
        "wk": "454486247185ce696b812d3472f66806ca65aa860d309be72910e855ecb0d2dd",
        "wo": "b1db383aa767fd8b6e64ff55ae2703e4c61161afcfd88e0664220f19ead1e903",
        "wq": "5b6d9a5d963878e12c59e3fb1bc3fc15d00e223fd24963eb16e24c82316f249b",
        "wv": "677be33279d0726eff64b5f1986c788d8f39d5e4713512e107f61ed6f06d7a80",
    },
}

# every field of the parent's ArchConfig for qwen2-1.5b
QWEN2_1_5B_ARCH = {
    'name': 'qwen2-1.5b', 'family': 'dense', 'num_layers': 28,
    'd_model': 1536, 'num_heads': 12, 'num_kv_heads': 2, 'd_ff': 8960,
    'vocab_size': 151936, 'head_dim': 128, 'num_experts': 0,
    'num_shared_experts': 0, 'top_k': 0, 'moe_d_ff': 0,
    'capacity_factor': 1.25, 'expert_sharding': 'ep', 'ssm_state': 0,
    'ssm_head_dim': 64, 'ssm_expand': 2, 'ssm_conv_width': 4,
    'ssm_chunk': 256, 'attn_every': 0, 'encoder_layers': 0,
    'decoder_layers': 0, 'num_prefix_embeds': 0, 'mlp_activation': 'silu',
    'qkv_bias': True, 'rope_theta': 1000000.0, 'norm_eps': 1e-05,
    'tie_embeddings': False, 'logits_softcap': 0.0, 'bayesian_head': True,
    'mc_samples': 10, 'head_init_sigma': 0.01, 'head_entropy': 'kernel',
    'decode_attn': 'gather', 'param_dtype': 'bfloat16',
    'moment_dtype': 'float32', 'remat': True, 'remat_group': 0,
    'seq_parallel': True, 'scan_layers': True, 'attn_q_chunk': 512,
    'attn_kv_chunk': 1024, 'fsdp_params': False, 'subquadratic': False}
# tiny.json's differs in these
TINY_ARCH = dict(QWEN2_1_5B_ARCH, **{
    'num_layers': 2, 'd_model': 64, 'num_heads': 4, 'd_ff': 128,
    'vocab_size': 2048, 'head_dim': 16, 'head_init_sigma': 0.05,
    'param_dtype': 'float32'})


def _digest(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(f"{a.dtype.name}{a.shape}".encode()
                          + a.tobytes()).hexdigest()


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_weights_are_bitwise_the_parents(seed):
    got = weights.generate(TINY, seed)
    assert {k: _digest(v) for k, v in got.items()} == DIGESTS[seed]


@pytest.mark.parametrize("config, want", [(TINY, TINY_ARCH),
                                          (QWEN2_1_5B, QWEN2_1_5B_ARCH)],
                         ids=["tiny", "qwen2-1.5b"])
def test_arch_config_is_the_parents_field_by_field(config, want):
    family = spec.family_module(config["reference"])
    assert dataclasses.asdict(family.arch_config(config)) == want
