"""moonshot-v1-16b-a3b-ep8 — Moonlight-16B-A3B as one chip of an
eight-way expert-parallel deployment holds it: every layer whole but the
routed experts, of which it holds experts 0-7 of 64.  The router still
routes over all 64; the layer computes these 8 experts' part.
"""
import dataclasses

from repro.configs.moonshot_v1_16b_a3b import CONFIG as FULL

CONFIG = dataclasses.replace(
    FULL, name="moonshot-v1-16b-a3b-ep8",
    moe=dataclasses.replace(FULL.moe, held=(0, 8)))
