"""Deterministic restartable data pipeline + synthetic test matrices."""
from .synthetic import (
    DataConfig,
    SyntheticLM,
    clustered_points,
    lowrank_plus_noise,
    powerlaw_matrix,
    sparse_matrix,
    spiked_decay_matrix,
    tune_rbf_sigma,
)
