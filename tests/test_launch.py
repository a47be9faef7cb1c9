"""The launchers as ``chip_smoke.py`` drives them, in-process, at the smoke
preset on CPU; and where the compilation cache goes."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch.compile_cache import use_compile_cache
from repro.launch.serve import serve
from repro.launch.train import train

ARCH = ["--arch", "granite-moe-1b-a400m"]


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    # with the variable set the launchers leave JAX's cache settings alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_train_launcher_in_process(no_cache):
    argv = ARCH + ["--steps", "2", "--log-every", "1", "--seq", "16",
                   "--batch", "4"]
    rep = train(argv)
    assert [h["step"] for h in rep["history"]] == [0, 1]
    losses = [h["loss"] for h in rep["history"]]
    assert np.all(np.isfinite(losses))
    assert rep["branch_delta"] > 0
    assert int(rep["state"]["step"]) == 2

    # two microbatches of the same global batch: the same first loss
    # (f32 smoke preset, so only summation order differs)
    micro = train(argv + ["--microbatch", "2"])
    np.testing.assert_allclose(micro["history"][0]["loss"], losses[0],
                               rtol=1e-5)


def test_serve_launcher_in_process(no_cache):
    rep = serve(ARCH + ["--batch", "2", "--prompt-len", "8", "--gen", "4"])
    assert rep["logits_finite"] and rep["cache_finite"]
    assert rep["tokens"].shape == (2, 4)
    assert np.all((rep["tokens"] >= 0) & (rep["tokens"] < rep["vocab"]))
    assert rep["decode_steps"] == 2 and rep["decode_tok_s"] > 0


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(Path(__file__).resolve().parents[1] /
                           ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
