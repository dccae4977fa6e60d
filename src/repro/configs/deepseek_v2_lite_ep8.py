"""DeepSeek-V2-Lite as one chip of an eight-way expert-parallel deployment
holds it: experts 0-7 of each MoE layer's 64, routed over all 64 with the
published top-6; attention, shared experts, the dense layer, embedding and
head whole; all 27 layers (model-configs guide, section 4)."""
import dataclasses

from . import deepseek_v2_lite as _full

CONFIG = dataclasses.replace(
    _full.CONFIG, name="deepseek-v2-lite-ep8",
    moe=dataclasses.replace(_full.CONFIG.moe, held=8, first_held=0),
)

SMOKE = dataclasses.replace(
    _full.SMOKE, name="deepseek-v2-lite-ep8-smoke",
    moe=dataclasses.replace(_full.SMOKE.moe, held=2, first_held=0),
)
