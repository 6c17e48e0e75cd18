"""End-to-end behaviour: full reconstructions through the public drivers,
and LM training that actually learns."""

import numpy as np
import pytest


def test_recon_driver_cgls():
    from repro.launch.recon import reconstruct
    _, rel = reconstruct("cgls", n=24, n_angles=48, iters=10, mode="plain",
                         verbose=False)
    assert rel < 0.45


def test_recon_driver_streaming_out_of_core():
    """The paper's headline: reconstruct a volume bigger than the (tiny,
    simulated) device memory budget."""
    from repro.launch.recon import reconstruct
    _, rel_s = reconstruct("ossart", n=24, n_angles=32, iters=3,
                           mode="stream", device_bytes=100 * 1024,
                           verbose=False)
    _, rel_p = reconstruct("ossart", n=24, n_angles=32, iters=3,
                           mode="plain", verbose=False)
    # the paper's claim: out-of-core == in-memory quality
    assert abs(rel_s - rel_p) < 1e-3, (rel_s, rel_p)
    assert rel_s < 0.6, rel_s


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_placement(tmp_path, monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left alone; otherwise the
    cache goes to the fixed ``<checkout>/.jax_cache``."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    saved = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache(tmp_path)
        if env_dir:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == saved
        else:
            assert got == str(tmp_path / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


@pytest.mark.slow
def test_lm_training_learns():
    """~0.4M-param LM on the synthetic pipeline: loss must drop
    substantially from its init value."""
    from repro.launch.train import train
    _, _, losses = train("stablelm-1.6b", steps=20, batch=8, seq=64,
                         verbose=False, lr=1e-3)
    first = np.mean(losses[:3])
    last = np.mean(losses[-3:])
    assert last < first - 0.5, (first, last)
