"""MoE / SSD / RG-LRU layers vs naive oracles; prefill↔decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import hybrid, layers as L, moe, ssm
from repro.obs import runtime

P32 = L.Policy(compute_dtype=jnp.float32)


# ----------------------------- SSD / mamba2 --------------------------------

def _ssd_inputs(key=0, b=2, s=32, h=4, p=8, g=2, n=16):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, g, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, g, n)) * 0.5
    return x, dt, A, B, C


@pytest.mark.parametrize("chunk", [4, 8, 32, 64])
def test_ssd_chunked_matches_recurrent_oracle(chunk):
    x, dt, A, B, C = _ssd_inputs()
    want, hf_want = ssm.ssd_reference(x, dt, A, B, C)
    got, hf_got = ssm._ssd_chunked(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf_got), np.asarray(hf_want),
                               rtol=1e-4, atol=1e-4)


def test_ssd_prefill_then_decode_consistent():
    """Running [0:24] chunked then 8 single-step decodes == full prefill."""
    x, dt, A, B, C = _ssd_inputs(s=32)
    full, hf = ssm._ssd_chunked(x, dt, A, B, C, chunk=8)
    y_pre, h = ssm._ssd_chunked(x[:, :24], dt[:, :24], A, B[:, :24],
                                C[:, :24], chunk=8)
    outs = [y_pre]
    for t in range(24, 32):
        y_t, h = ssm._ssd_chunked(x[:, t:t + 1], dt[:, t:t + 1], A,
                                  B[:, t:t + 1], C[:, t:t + 1], chunk=8, h0=h)
        outs.append(y_t)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hf),
                               rtol=1e-4, atol=1e-4)


def test_ssd_block_end_to_end():
    cfg = ssm.SSDConfig(d_model=32, d_state=16, headdim=8, expand=2, chunk=8)
    params = ssm.ssd_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))
    y, _ = ssm.ssd_block(params, x, cfg, policy=P32)
    assert y.shape == x.shape and np.all(np.isfinite(np.asarray(y)))
    # stateful decode matches stateless prefill
    st = ssm.ssd_state_init(cfg, batch=2)
    outs = []
    for t in range(16):
        o, st = ssm.ssd_block(params, x[:, t:t + 1], cfg, policy=P32, state=st)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(y),
                               rtol=2e-3, atol=2e-3)


def test_ssd_gradients_finite():
    cfg = ssm.SSDConfig(d_model=16, d_state=8, headdim=8, expand=2, chunk=4)
    params = ssm.ssd_init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, 16))
    g = jax.grad(lambda p: jnp.sum(ssm.ssd_block(p, x, cfg, policy=P32)[0] ** 2)
                 )(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.all(np.isfinite(np.asarray(leaf)))


# ----------------------------- RG-LRU --------------------------------------

def test_rg_lru_scan_matches_recurrence():
    cfg = hybrid.LRUConfig(d_model=16, lru_width=24)
    params = hybrid.lru_init(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 20, 24))
    got, hf_got = hybrid._rg_lru(params, x, P32)
    want, hf_want = hybrid.rg_lru_reference(params, x, P32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hf_got), np.asarray(hf_want),
                               rtol=1e-5, atol=1e-5)


def test_lru_block_prefill_decode_consistent():
    cfg = hybrid.LRUConfig(d_model=16, lru_width=16)
    params = hybrid.lru_init(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 10, 16))
    full, _ = hybrid.lru_block(params, x, cfg, policy=P32)
    st = hybrid.lru_state_init(cfg, batch=2)
    outs = []
    for t in range(10):
        o, st = hybrid.lru_block(params, x[:, t:t + 1], cfg, policy=P32,
                                 state=st)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_rg_lru_chunked_scan_matches_full():
    """§Perf H2: chunked scan (O(chunk) temporaries) is numerically the
    same recurrence, including carried state and ragged tails."""
    cfg = hybrid.LRUConfig(d_model=16, lru_width=24)
    params = hybrid.lru_init(jax.random.PRNGKey(20), cfg)
    x = jax.random.normal(jax.random.PRNGKey(21), (2, 37, 24))
    h0 = jax.random.normal(jax.random.PRNGKey(22), (2, 24)) * 0.1
    full, hf_full = hybrid._rg_lru(params, x, P32, h0=h0)
    for chunk in (4, 8, 16, 64):
        got, hf = hybrid._rg_lru(params, x, P32, h0=h0, scan_chunk=chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_full),
                                   rtol=1e-5, atol=1e-5)


def test_lru_state_bounded():
    """|a|<1 keeps the state bounded over long rollouts (retention analogue)."""
    cfg = hybrid.LRUConfig(d_model=8, lru_width=8)
    params = hybrid.lru_init(jax.random.PRNGKey(9), cfg)
    x = jnp.ones((1, 500, 8))
    y, hf = hybrid._rg_lru(params, x, P32)
    assert float(jnp.max(jnp.abs(hf))) < 100.0


# ----------------------------- MoE ------------------------------------------

def _moe_setup(key=0, e=4, k=2, b=2, s=16, d=8, f=16, cf=2.0):
    cfg = moe.MoEConfig(d_model=d, d_ff=f, n_experts=e, top_k=k,
                        capacity_factor=cf, group_size=16)
    params = moe.moe_init(jax.random.PRNGKey(key), cfg)
    x = jax.random.normal(jax.random.PRNGKey(key + 1), (b, s, d))
    return cfg, params, x


def test_moe_shapes_and_aux():
    cfg, params, x = _moe_setup()
    y, aux = moe.moe_apply(params, x, cfg, policy=P32)
    assert y.shape == x.shape
    assert float(aux) >= 1.0 - 1e-3  # aux loss lower bound is 1 at balance


def test_moe_matches_dense_reference_with_ample_capacity():
    """With capacity ≥ tokens, MoE == Σ_k gate_k · expert_k(x) exactly."""
    cfg, params, x = _moe_setup(cf=100.0)  # nothing dropped
    y, _ = moe.moe_apply(params, x, cfg, policy=P32)

    logits = x @ params["router"]["w"]
    gates = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(gates, cfg.top_k)
    topv = topv / topv.sum(-1, keepdims=True)

    def expert(e_idx, v):
        h = jax.nn.silu(v @ params["wg"][e_idx]) * (v @ params["wi"][e_idx])
        return h @ params["wo"][e_idx]

    want = jnp.zeros_like(x)
    for kk in range(cfg.top_k):
        idx = topi[..., kk]
        out = jax.vmap(jax.vmap(expert))(idx, x)
        want = want + topv[..., kk:kk + 1] * out
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_tokens_gracefully():
    cfg, params, x = _moe_setup(cf=0.25)  # aggressive dropping
    y, _ = moe.moe_apply(params, x, cfg, policy=P32)
    assert np.all(np.isfinite(np.asarray(y)))


def test_moe_top1_shared_expert():
    cfg = moe.MoEConfig(d_model=8, d_ff=16, n_experts=4, top_k=1,
                        group_size=16, shared_expert=True)
    params = moe.moe_init(jax.random.PRNGKey(10), cfg)
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 8, 8))
    y, _ = moe.moe_apply(params, x, cfg, policy=P32)
    assert y.shape == x.shape and np.all(np.isfinite(np.asarray(y)))


def test_moe_gradients_flow_to_router_and_experts():
    cfg, params, x = _moe_setup()
    g = jax.grad(lambda p: jnp.sum(moe.moe_apply(p, x, cfg, policy=P32)[0] ** 2)
                 )(params)
    assert float(jnp.max(jnp.abs(g["router"]["w"]))) > 0
    assert float(jnp.max(jnp.abs(g["wi"]))) > 0


# the same tokens through both routes: (experts, top_k, capacity factor,
# batch, seq, group, shared expert)
ROUTE_CASES = {
    "no-drops": (4, 2, 100.0, 2, 16, 16, False),
    "heavy-drops": (4, 2, 0.25, 2, 16, 16, False),
    "padded": (4, 2, 1.25, 3, 10, 16, False),       # 30 tokens, groups of 16
    "groups": (8, 2, 1.25, 4, 32, 16, False),
    "top1": (8, 1, 1.25, 2, 32, 32, False),
    "top8": (8, 8, 1.25, 2, 32, 32, False),
    "shared": (4, 1, 1.25, 2, 16, 16, True),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(ROUTE_CASES))
def test_moe_index_route_matches_onehot_route(case, dtype):
    e, k, cf, b, s, g, shared = ROUTE_CASES[case]
    cfg = moe.MoEConfig(d_model=8, d_ff=16, n_experts=e, top_k=k,
                        capacity_factor=cf, group_size=g,
                        shared_expert=shared)
    params = moe.moe_init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (b * s, 8))
    x = jnp.pad(x, ((0, -(b * s) % g), (0, 0))).reshape(-1, g, 8)
    policy = L.Policy(compute_dtype=dtype)

    def run(route, params):
        return route(params, x, cfg, policy=policy, bfp=L.NO_BFP)

    (want, aux_o), (got, aux_i) = (jax.jit(run, static_argnums=0)(r, params)
                                   for r in (moe._onehot_route,
                                             moe._index_route))
    assert got.dtype == want.dtype == dtype and float(aux_i) == float(aux_o)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

        def grads(route):
            return jax.jit(jax.grad(
                lambda p: jnp.sum(run(route, p)[0] ** 2)))(params)
        for gi, go in zip(jax.tree_util.tree_leaves(grads(moe._index_route)),
                          jax.tree_util.tree_leaves(grads(moe._onehot_route))):
            np.testing.assert_allclose(gi, go, rtol=1e-5, atol=1e-5)
    else:
        # the one-hot route rounds each gate, their sum and each weight to
        # bf16; the index route normalises in f32 and rounds each weight
        eps = float(jnp.finfo(jnp.bfloat16).eps)
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=2 * eps * np.abs(want).max())


def test_moe_apply_routes_by_group_size_and_records_it():
    runtime.record_compiles()
    n = moe._INDEX_ROUTE_MIN_GROUP
    cfg = moe.MoEConfig(d_model=8, d_ff=16, n_experts=4, top_k=2,
                        group_size=n, shared_expert=True)
    params = moe.moe_init(jax.random.PRNGKey(5), cfg)

    def moe_route_probe(x):
        return moe.moe_apply(params, x, cfg, policy=P32)[0]

    seen = []
    for tokens in (n - 1, n, 4 * n):
        jax.jit(moe_route_probe).lower(jnp.ones((1, tokens, 8)))
        r = runtime.moe_routes_of("moe_route_probe")[-1]
        seen.append((r.name, r.args["group"], r.args["experts"],
                     r.args["capacity"]))
    assert [s[:2] for s in seen] == [("onehot", n - 1), ("index", n),
                                     ("index", n)]
    assert all(s[2:] == (4, moe.capacity(cfg, s[1])) for s in seen)
