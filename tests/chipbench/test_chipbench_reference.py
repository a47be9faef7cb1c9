"""The plain reference (``chipbench/reference/transformer.py``) against the
program at the smoke widths, on the CPU, with the program computing in
float32 so that only summation order separates the two."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common, weights
from chipbench.reference import transformer as R
from chipbench.tokens import PROMPTS, ZipfSource

DATA = Path(__file__).resolve().parent / "data" / "configs"


def _cfg(name):
    return json.loads((DATA / f"{name}.json").read_text())


def test_layer_slice_is_the_stacked_leaf():
    init = _cfg("tiny-moe")["init"]
    key = weights.seed_key(2**33 + 5)
    path, shape = "stack/sub0/moe/wi", (3, 4, 8, 16)
    whole = weights.leaf(key, "backbone", path, shape, init)
    for i in range(3):
        np.testing.assert_array_equal(
            whole[i], weights.layer_slice(key, "backbone", path, shape,
                                          init, i))


def test_bfp_matches_the_program_bit_for_bit():
    from repro.core.bfp import bfp_qdq
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 80)) * 0.3
    x = x.at[:32, :32].set(0.0).at[40, 50].set(300.0)
    np.testing.assert_array_equal(R.bfp(x, 32, 4, 5),
                                  bfp_qdq(x, (32, 32), 4, 5))


def _program_train(cfg, seed, batches):
    """The program's duplex step in float32, from the harness's weights."""
    from repro.launch.cells import duplex_tcfg
    from repro.models import layers as L
    from repro.train import train_step as ts
    entry, mc = common.program_config(cfg)
    tcfg = duplex_tcfg(mc, backbone_dtype=jnp.float32)
    policy = L.Policy(compute_dtype=jnp.float32)
    shapes = jax.eval_shape(lambda k: ts.init_state(k, entry, mc, tcfg,
                                                    policy),
                            jax.random.PRNGKey(0))
    key = weights.seed_key(seed)
    state = {"step": jnp.zeros((), jnp.int32),
             "backbone": weights.make_tree(key, "backbone",
                                           shapes["backbone"], cfg["init"]),
             "branch": weights.make_tree(key, "branch", shapes["branch"],
                                         cfg["init"]),
             "opt": jax.tree_util.tree_map(jnp.zeros_like, shapes["opt"])}
    step = jax.jit(ts.make_train_step(entry, mc, tcfg, policy))
    b0, losses = state["branch"], []
    for i, batch in enumerate(batches):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        if i == 0:
            mu = state["opt"]["mu"]
    change = jax.tree_util.tree_map(lambda a, b: a - b, state["branch"], b0)
    return {"losses": losses, "grad": common.leaf_norms(mu),
            "change": common.leaf_norms(change)}


@pytest.mark.parametrize("name", ["tiny-moe", "tiny-dense"])
def test_train_steps_agree(name):
    cfg = _cfg(name)
    seed = 12345
    batches = [ZipfSource(seed, 2, 64, cfg["vocab_size"]).batch(i)
               for i in range(3)]
    prog = _program_train(cfg, seed, batches)
    ref = R.Reference(cfg, seed).train(batches, 3)
    # float32 on both sides: losses to a few ulps of their sum over 128
    # tokens; block floating point may flip one element's rounding, which
    # moves a gradient's norm by well under a part in a thousand
    np.testing.assert_allclose(prog["losses"], ref["losses"], rtol=1e-5)
    assert common.worst_leaf_gap(prog["grad"], ref["grad"]) < 1e-3
    assert common.worst_leaf_gap(prog["change"], ref["change"]) < 1e-3


def test_prefill_and_decode_logits_agree():
    """Prefill's next-token logits and each decode step's logits through
    the cache equal the reference's full forward pass, with the MoE's
    groups as the program dispatched them (prompts in groups of 64 tokens,
    each decode step one group of the batch) and tokens dropped past
    capacity on both sides."""
    from repro.models import layers as L
    from repro.train import serve_step as ss
    cfg = _cfg("tiny-moe")
    # a capacity factor of 1: decode groups of 8 tokens hold 4 choices per
    # expert, and prompts' groups of 64 hold 32, so choices are dropped
    cfg["capacity_factor"] = 1.0
    cfg["program"] = dict(cfg["program"], overrides={"capacity_factor": 1.0})
    seed, b, plen, gen = 99, 8, 32, 8
    entry, mc = common.program_config(cfg)
    policy = L.Policy(compute_dtype=jnp.float32)
    shapes = jax.eval_shape(lambda k: entry.module.init_params(k, mc),
                            jax.random.PRNGKey(0))
    params = weights.make_tree(weights.seed_key(seed), "backbone", shapes,
                               cfg["init"])
    prompts = ZipfSource(seed, b, plen, cfg["vocab_size"],
                         stream=PROMPTS).batch(0)["tokens"]
    prefill = jax.jit(ss.make_prefill_step(entry, mc, max_len=plen + gen,
                                           policy=policy,
                                           cache_dtype=jnp.float32,
                                           logits_mode="last"))
    decode = jax.jit(lambda p, c, t: entry.module.decode_step(
        p, mc, t, c, policy=policy))
    out = prefill(params, prompts, None)
    logits, cache = [out["next_token_logits"]], out["cache"]
    tok = jnp.argmax(logits[0], -1)[:, None].astype(jnp.int32)
    served = [tok]
    for _ in range(gen - 1):
        lg, cache = decode(params, cache, tok)
        logits.append(lg[:, -1])
        tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
        served.append(tok)
    prog = np.stack([np.asarray(x)[:, :cfg["vocab_size"]] for x in logits], 1)
    seqs = np.concatenate([prompts] + [np.asarray(t) for t in served], 1)

    ref = R.Reference(cfg, seed)
    hidden = ref.served_hidden(seqs, plen)
    want = np.asarray(jnp.einsum("bgd,vd->bgv", hidden, ref.table,
                                 precision=R.HI))
    # float32 both sides; logits are of order 0.1
    np.testing.assert_allclose(prog, want, atol=2e-5)
    best, got, pick = ref.logit_stats(hidden, seqs[:, plen:])
    np.testing.assert_array_equal(np.asarray(pick), seqs[:, plen:])
    assert float(jnp.max(best - got)) == 0.0


def test_decode_groups_drop_past_capacity():
    """Decode groups of 8 tokens, 4 experts, top 2 and capacity factor 1
    hold 4 choices per expert: the choices past them are dropped."""
    cfg = dict(_cfg("tiny-moe"), capacity_factor=1.0)
    assert R.capacity(8, 2, 4, cfg["capacity_factor"]) == 4
    xg = jnp.ones((1, 8, 32))
    p = {"moe/router/w": jnp.zeros((32, 4)).at[0, 0].set(1.0)
         .at[0, 1].set(0.5),
         "moe/wi": jnp.ones((4, 32, 16)), "moe/wg": jnp.ones((4, 32, 16)),
         "moe/wo": jnp.ones((4, 16, 32))}
    y = R.moe_groups(p, xg, cfg, None)
    # every token picks experts 0 and 1; only the first 4 tokens fit
    assert float(jnp.abs(y[0, 4:]).max()) == 0.0
    assert float(jnp.abs(y[0, :4]).min()) > 0.0


def test_gap_over_distinct_contexts_counts_a_loop_once():
    """A sequence stuck in a loop repeats one near tie: counted by context,
    the loop weighs as one position, where the plain mean counts it at
    every repeat."""
    from chipbench.jobs import serve
    loop = np.tile([5, 6], 8)                                  # 16 tokens
    fresh = np.arange(16)
    tokens = np.stack([loop, fresh])[None]                     # [1, 2, 16]
    gaps = np.zeros((1, 2, 16))
    gaps[0, 0, 8::2] = 0.5                  # the loop's tie, at each repeat
    s = serve.gap_stats(gaps, tokens)
    assert s["logit_gap"] == 0.5 and s["mismatch_share"] == 4 / 32
    assert s["logit_gap_mean"] == pytest.approx(2.0 / 32)
    # the loop has 4 + 2 distinct contexts: its first four, then two that
    # repeat, one of which holds the gap half of the time
    n = 6 + 16
    assert s["logit_gap_distinct"] == pytest.approx((0.5 * 4 / 6) / n)
