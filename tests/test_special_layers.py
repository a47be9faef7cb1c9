"""MoE / SSD / RG-LRU layers vs naive oracles; prefill↔decode consistency."""
import dataclasses

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


# (groups, length, chunk): lengths that are and are not a multiple of it
SSD_CASES = [(1, 32, 8), (1, 30, 8), (2, 30, 8), (1, 30, 16), (2, 45, 16),
             (1, 5, 16)]


@pytest.mark.parametrize("g,s,chunk", SSD_CASES)
@pytest.mark.parametrize("carried", [False, True])
def test_ssd_chunked_cases(g, s, chunk, carried):
    """The chunked scan against the recurrence with one or two groups of B
    and C, ragged lengths, two chunk sizes, and a state carried in from 11
    tokens before (``h0``)."""
    x, dt, A, B, C = _ssd_inputs(key=7, s=s + 11, g=g)
    want, hf_want = ssm.ssd_reference(x, dt, A, B, C)
    if carried:
        _, h0 = ssm.ssd_reference(*(a[:, :11] for a in (x, dt)), A,
                                  *(a[:, :11] for a in (B, C)))
        rest = [a[:, 11:] for a in (x, dt, B, C)]
        got, hf_got = ssm._ssd_chunked(*rest[:2], A, *rest[2:], chunk, h0=h0)
        want = want[:, 11:]
    else:
        got, hf_got = ssm._ssd_chunked(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(hf_got, hf_want, rtol=1e-4, atol=1e-4)


def test_ssd_chunk_is_a_tiling_choice():
    x, dt, A, B, C = _ssd_inputs(key=9, s=48, g=1)
    outs = [ssm._ssd_chunked(x, dt, A, B, C, c) for c in (8, 16, 48)]
    for y, h in outs[1:]:
        np.testing.assert_allclose(y, outs[0][0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(h, outs[0][1], rtol=1e-5, atol=1e-5)


def test_ssd_plan_records_the_chunk_temporary():
    runtime.record_compiles()
    x, dt, A, B, C = _ssd_inputs(s=40, g=1)

    def ssd_plan_probe(x):
        return ssm._ssd_chunked(x, dt, A, B, C, 16)[0]

    jax.jit(ssd_plan_probe).lower(x)
    r = runtime.ssd_plans_of("ssd_plan_probe")[-1]
    b, _, h, _ = x.shape
    assert r.args == {"chunk": 16, "heads": h, "groups": 1,
                      "temp_bytes": b * h * 16 * 16 * 4}


def test_ssd_block_gates_then_normalises():
    """Mamba-2's mixer, written out: x, B, C through the causal conv and
    SiLU, dt = softplus(dt + bias), the SSD, y + D·x, then the gated
    RMSNorm rmsnorm(y · silu(z)) · scale over d_inner, then out_proj."""
    cfg = ssm.SSDConfig(d_model=16, d_state=8, headdim=4, expand=2, chunk=4,
                        eps=1e-5)
    params = ssm.ssd_init(jax.random.PRNGKey(21), cfg)
    params["norm"]["scale"] = jax.random.uniform(jax.random.PRNGKey(22),
                                                 (cfg.d_inner,)) + 0.5
    u = jax.random.normal(jax.random.PRNGKey(23), (2, 12, 16))
    y, _ = ssm.ssd_block(params, u, cfg, policy=P32)

    def conv(name, v):
        return ssm._causal_conv(v, params[name]["w"], params[name]["b"])[0]

    z = u @ params["z_proj"]["w"]
    xs = conv("conv_x", u @ params["x_proj"]["w"]).reshape(2, 12, -1, 4)
    B = conv("conv_b", u @ params["b_proj"]["w"])[:, :, None]
    C = conv("conv_c", u @ params["c_proj"]["w"])[:, :, None]
    dt = jax.nn.softplus(u @ params["dt_proj"]["w"] + params["dt_bias"])
    ys, _ = ssm.ssd_reference(xs, dt, -jnp.exp(params["A_log"]), B, C)
    ys = (ys + xs * params["D"][:, None]).reshape(2, 12, -1)
    g = ys * jax.nn.silu(z)
    g = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
    want = (g * params["norm"]["scale"]) @ params["out_proj"]["w"]
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)


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


@pytest.mark.parametrize("cf", [100.0, 1.0])
@pytest.mark.parametrize("route", ["onehot", "index"])
def test_moe_shares_add_up_to_the_whole_layer(route, cf):
    """Eight layers that each hold 2 of a router's 16 experts (its share of
    an expert-parallel group), with the shared expert counted once, give
    the whole layer's output: the same choices kept past capacity, the
    gates normalised over all of them."""
    e, k, held = 16, 4, 2
    whole = moe.MoEConfig(d_model=8, d_ff=16, n_experts=e, top_k=k,
                          capacity_factor=cf, group_size=32,
                          shared_expert=True, shared_d_ff=24)
    params = moe.moe_init(jax.random.PRNGKey(30), whole)
    x = jax.random.normal(jax.random.PRNGKey(31), (2, 32, 8))

    def apply(cfg, p):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe, "_INDEX_ROUTE_MIN_GROUP", 32 if route == "index"
                       else 10**9)
            return moe.moe_apply(p, x, cfg, policy=P32)[0]

    want = apply(whole, params)
    parts = L.mlp(params["shared"], x, policy=P32)
    for i in range(e // held):
        share = dataclasses.replace(whole, n_experts=held, n_routed=e,
                                    offset=held * i, shared_expert=False)
        p = {"router": params["router"]}
        p.update({n: params[n][held * i:held * (i + 1)]
                  for n in ("wi", "wg", "wo")})
        parts = parts + apply(share, p)
    np.testing.assert_allclose(parts, want, rtol=1e-5, atol=1e-5)


def test_moe_records_the_experts_held():
    runtime.record_compiles()
    cfg = moe.MoEConfig(d_model=8, d_ff=16, n_experts=3, top_k=2, n_routed=12,
                        offset=6, group_size=16)
    params = moe.moe_init(jax.random.PRNGKey(5), cfg)
    assert params["router"]["w"].shape == (8, 12)
    assert params["wi"].shape == (3, 8, 16)

    def moe_share_probe(x):
        return moe.moe_apply(params, x, cfg, policy=P32)[0]

    jax.jit(moe_share_probe).lower(jnp.ones((1, 16, 8)))
    r = runtime.moe_routes_of("moe_share_probe")[-1]
    assert (r.args["experts"], r.args["routed"], r.args["offset"],
            r.args["capacity"]) == (3, 12, 6, moe.capacity(cfg, 16))
    assert moe.capacity(cfg, 16) == 4       # 16 tokens · 2 / 12 experts
