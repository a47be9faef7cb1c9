"""Per-architecture smoke tests (reduced configs): one forward + one train
step + one decode step on CPU; asserts shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L, registry

P32 = L.Policy(compute_dtype=jnp.float32)
B, S = 2, 16


def _frontend(entry, cfg, batch, key=11):
    shapes = entry.frontend_shape(cfg, batch)
    if shapes is None:
        return None
    return {k: jax.random.normal(jax.random.PRNGKey(key), v) * 0.1
            for k, v in shapes.items()}


@pytest.mark.parametrize("arch", sorted(registry.ARCHS))
def test_forward_and_grad(arch):
    entry = registry.get(arch)
    cfg = entry.smoke
    params = entry.module.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    frontend = _frontend(entry, cfg, B)

    kw = {} if frontend is None else {"frontend": frontend}
    out = entry.module.forward(params, cfg, tokens, policy=P32, **kw)
    hidden = out["hidden"]
    assert hidden.shape == (B, S, cfg.d_model)
    assert np.all(np.isfinite(np.asarray(hidden, dtype=np.float32)))

    logits = entry.module.lm_logits(params, cfg, hidden, P32)
    assert logits.shape[-1] >= cfg.vocab
    assert np.all(np.isfinite(np.asarray(logits[..., :cfg.vocab])))

    # one training-gradient step on the full model (family sanity)
    def loss_fn(p):
        o = entry.module.forward(p, cfg, tokens, policy=P32, **kw)
        lg = entry.module.lm_logits(p, cfg, o["hidden"], P32)
        lp = jax.nn.log_softmax(lg, axis=-1)
        tgt = jnp.roll(tokens, -1, axis=1)
        nll = -jnp.take_along_axis(lp, tgt[..., None], -1).mean()
        return nll + 0.01 * o.get("aux", 0.0)

    val, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(val))
    gmax = max(float(jnp.max(jnp.abs(g)))
               for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gmax) and gmax > 0


@pytest.mark.parametrize("arch", sorted(registry.ARCHS))
def test_prefill_then_decode_matches_forward(arch):
    """Prefill[0:S-1] + decode step S-1 ≈ full forward's last-token logits."""
    entry = registry.get(arch)
    cfg = entry.smoke
    params = entry.module.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab)
    frontend = _frontend(entry, cfg, B)
    kw = {} if frontend is None else {"frontend": frontend}

    out = entry.module.forward(params, cfg, tokens, policy=P32, **kw)
    full_logits = entry.module.lm_logits(params, cfg, out["hidden"], P32)

    pre = entry.module.prefill(params, cfg, tokens[:, :S - 1], max_len=S + 4,
                               policy=P32, cache_dtype=jnp.float32, **kw)
    np.testing.assert_allclose(
        np.asarray(pre["logits"][:, -1, :cfg.vocab]),
        np.asarray(full_logits[:, S - 2, :cfg.vocab]), rtol=2e-3, atol=2e-3)

    step_logits, cache = entry.module.decode_step(
        params, cfg, tokens[:, S - 1:S], pre["cache"], policy=P32)
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0, :cfg.vocab]),
        np.asarray(full_logits[:, S - 1, :cfg.vocab]), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", sorted(registry.ARCHS))
def test_zero_init_cache_decode_runs(arch):
    """The dry-run decode entry point: fresh zero cache + one step."""
    entry = registry.get(arch)
    cfg = entry.smoke
    params = entry.module.init_params(jax.random.PRNGKey(4), cfg)
    cache = entry.module.init_cache(cfg, batch=B, max_len=S,
                                    dtype=jnp.float32)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, new_cache = entry.module.decode_step(params, cfg, tok, cache,
                                                 policy=P32)
    assert logits.shape[0] == B
    assert np.all(np.isfinite(np.asarray(logits[..., :cfg.vocab])))


def test_registry_cells_cover_40():
    all_cells = registry.cells(include_skips=True)
    assert len(all_cells) == 44
    skipped = [c for c in all_cells if c[2] is not None]
    # 9 archs with quadratic attention layers skip long_500k (granite-4.0-h
    # among them, for its 4 of 40); ssm + recurrentgemma run it
    assert len(skipped) == 9
    runnable = {(a, s.name) for a, s, k in all_cells if k is None}
    assert ("mamba2-780m", "long_500k") in runnable
    assert ("recurrentgemma-9b", "long_500k") in runnable
