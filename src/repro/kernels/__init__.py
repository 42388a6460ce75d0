"""Pallas TPU kernels for the paper's compute hot spots.

twoside_sketch — fused S_C·A·S_Rᵀ (Algorithm 1/3 inner sketch)
countsketch    — input-sparsity CountSketch: each signed row of A added into
                 its bucket row of a VMEM accumulator (CountSketch.apply on TPU)
panel_score    — fused streaming panel scoring: S_C·A_L + column energies +
                 admitted-basis residuals in one VMEM pass (adaptive CUR)
panel_update   — fused panel-update megakernel: panel_score's triple plus
                 the in-kernel admission decision, the M fold and the C
                 scatter, with C/M aliased in place (adaptive CUR)
Each has a pure-jnp oracle in ref.py; ops.py holds the jit'd wrappers and
the shared padding/dispatch scheme (pad_dims / interpret_default).
"""
from .ops import (
    countsketch_apply,
    countsketch_ref,
    kernel_route_enabled,
    panel_score,
    panel_score_ref,
    panel_update,
    panel_update_ref,
    twoside_sketch,
    twoside_sketch_ref,
)
