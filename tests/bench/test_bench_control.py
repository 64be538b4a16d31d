"""The control at a size a test run holds: the reference one precision
lower (float8 body, bfloat16 head) in the program's place fails the
comparison that the float32 reference in its place passes.

The chip cells' control runs at their own size (``bench/control.py``);
here the configuration is a 16-layer, 512-wide Qwen2 with an 8,192-entry
head whose noise matches the cells' (sigma * |x| about 0.4), and the
served sequences are 4 random prompts of 384 tokens with 48 tokens each.
The limit is set from these readings on CPU (float32 stand-in up to
0.048, control from 0.099 on seeds 1-3), as the cells' limits are from
the chip's.
"""

import json

import numpy as np
import pytest

from _paths import DATA
from bench import check, spec, weights

CONFIG = json.loads((DATA / "small.json").read_text())
LIMITS = {"gap_mean": {"limit": 0.075}}


def _served(seed):
    rng = np.random.default_rng(seed)
    V = CONFIG["vocab_size"]
    return [{"prompt": rng.integers(0, V, 384).astype(np.int32),
             "tokens": rng.integers(0, V, 48).astype(np.int32)}
            for _ in range(4)]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_float32_passes(seed):
    ref = spec.reference_module(CONFIG["reference"])
    w = weights.generate(CONFIG, seed)
    served = _served(seed)
    sound = check.numbers(check.readings(ref, w, CONFIG, served, seed,
                                         stand_in="float32"))
    control = check.numbers(check.readings(ref, w, CONFIG, served, seed,
                                           stand_in="control"))
    assert check.judge(sound, LIMITS)[0]
    ok, lines = check.judge(control, LIMITS)
    assert not ok, lines
