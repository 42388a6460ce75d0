"""End-to-end system behaviour: train → checkpoint → restore → serve."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore, run_resilient_loop, latest_step
from repro.configs import ARCHS, supported_cells
from repro.data import DataConfig, SyntheticLM
from repro.models import init_params
from repro.serve import generate
from repro.train import OptimizerConfig, init_opt_state, make_train_step


@pytest.mark.slow
def test_train_checkpoint_restore_serve(tmp_path):
    """The full lifecycle on one device: loss falls, crash mid-run recovers
    from checkpoint, the final model serves tokens deterministically."""
    cfg = dataclasses.replace(
        ARCHS["llama3.2-1b"].smoke_config(), d_model=64, d_ff=256, vocab_size=128
    )
    oc = OptimizerConfig(lr=5e-3, warmup_steps=2, total_steps=40)
    params = init_params(jax.random.key(0), cfg)
    state = {"params": params, "opt": init_opt_state(params, oc)}
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, batch=8, seq_len=64))
    jstep = jax.jit(make_train_step(cfg, oc, remat=None), donate_argnums=(0,))

    report = run_resilient_loop(
        state=state,
        step_fn=lambda s, b, i: jstep(s, b),
        batch_fn=data.batch_at,
        n_steps=30,
        ckpt_dir=str(tmp_path),
        ckpt_every=10,
        fail_at_step=17,  # injected crash mid-run
    )
    assert report.restarts == 1
    assert report.losses[-1] < report.losses[0] - 0.3
    assert latest_step(str(tmp_path)) == 30

    # restore and serve
    final, extra, step = restore(str(tmp_path), state)
    prompt = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    out1 = generate(final["params"], cfg, prompt, 8)
    out2 = generate(final["params"], cfg, prompt, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 8)


def test_assigned_cell_coverage():
    """40 assigned (arch × shape) cells: 33 runnable + 7 documented skips
    (pure full-attention archs × long_500k)."""
    cells = supported_cells()
    assert len(cells) == 40
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(runnable) == 33
    assert all(shape == "long_500k" for _, shape, _ in skipped)
    skip_archs = {a for a, _, _ in skipped}
    assert skip_archs == {
        "kimi-k2-1t-a32b", "deepseek-v2-lite-16b", "llama3.2-1b", "phi4-mini-3.8b",
        "mistral-nemo-12b", "musicgen-large", "llama-3.2-vision-90b",
    }
