"""granite-4.0-h-small-ep8: what one chip holds of Granite-4.0-H-Small
served as 4 pipeline stages of one period (10 layers), each stage's MoE
split 8-way expert parallel (32 chips).  This chip is stage 1, EP rank 0:
layers 0–9 (9 Mamba-2 layers, attention at layer 5), routed experts 0–8
of each (the router keeps its 72 outputs and top-10; what experts 9–71
would add is left out), the shared expert, the attention and the tied
vocabulary whole.  Every width is the published one."""
import dataclasses

from repro.configs import granite_4_0_h_small
from repro.models.common import ModelConfig


def config() -> ModelConfig:
    return dataclasses.replace(
        granite_4_0_h_small.config(), name="granite-4.0-h-small-ep8",
        n_layers=10, moe_n_held=9, moe_held_offset=0,
    )
