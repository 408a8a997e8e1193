"""LM generation loop + training-launcher fault-tolerance integration."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.models.model import init_params
from repro.models.generate import generate


@pytest.mark.parametrize("arch", ["mamba2-130m", "qwen3-14b", "hymba-1.5b"])
def test_generate_shapes(arch):
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    out = generate(params, cfg, prompt, max_new_tokens=6)
    assert out.shape == (2, 14)
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab)))
    # prompt is preserved
    np.testing.assert_array_equal(np.asarray(out[:, :8]),
                                  np.asarray(prompt))


def test_generate_greedy_deterministic():
    cfg = get_config("mamba2-130m", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
    a = generate(params, cfg, prompt, max_new_tokens=8)
    b = generate(params, cfg, prompt, max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_launcher_checkpoint_resume(tmp_path):
    """Kill-and-resume: the launcher restarts from the atomic checkpoint."""
    from repro.launch.train import main
    ck = str(tmp_path / "ck")
    rc = main(["--arch", "mamba2-130m", "--steps", "4", "--batch", "2",
               "--seq", "32", "--ckpt", ck, "--ckpt-every", "2"])
    assert rc == 0
    from repro.checkpoint.manager import CheckpointManager
    assert CheckpointManager(ck).latest_step() == 4
    # relaunch with more steps: resumes at 4, runs to 6
    rc = main(["--arch", "mamba2-130m", "--steps", "6", "--batch", "2",
               "--seq", "32", "--ckpt", ck, "--ckpt-every", "2"])
    assert rc == 0
    assert CheckpointManager(ck).latest_step() == 6
