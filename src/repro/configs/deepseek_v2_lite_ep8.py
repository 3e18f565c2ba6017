"""deepseek-v2-lite-ep8: what one chip holds of DeepSeek-V2-Lite served as
3 pipeline stages of 9 layers, each stage's MoE layers split 8-way expert
parallel (24 chips).  This chip is stage 1, EP rank 0: the dense layer 0
and MoE layers 1–8, routed experts 0–7 of each (the router keeps its 64
outputs and top-6; what experts 8–63 would add is left out), attention,
router, shared experts and the dense layer whole, and the whole
vocabulary for the embedding and the untied head.  Every width is the
published one."""
import dataclasses

from repro.configs import deepseek_v2_lite
from repro.models.common import ModelConfig


def config() -> ModelConfig:
    return dataclasses.replace(
        deepseek_v2_lite.config(), name="deepseek-v2-lite-ep8",
        n_layers=9, moe_n_held=8, moe_held_offset=0,
    )
