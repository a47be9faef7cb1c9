"""Closed-loop serving in rounds: the program's jitted prefill and decode
steps, as its serving launcher builds them.

Traffic keys: ``batch`` (sequences per round), ``prompt_len``, ``gen``
(tokens served per sequence: one from prefill, ``gen - 1`` decode steps),
``zipf_a``.  Each round takes ``batch`` fresh seeded prompts, prefills them
once and decodes greedily; every token is fetched to the host as it is
made, as a streaming server must.  Whole rounds run until ``--seconds``
have passed.

After the window every finished round is checked: the reference runs over
each prompt with its served tokens and reads, at each served position, how
far the served token's logit lies below its own best (``gap_stats``).  The
numbers that the cell's limits name are compared.
"""
from __future__ import annotations

import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, weights, work
from chipbench.common import span
from chipbench.tokens import PROMPTS, ZipfSource


def run(cell, seed: int, seconds: float, trace: bool, clock, hooks=None,
        control: bool = False) -> common.Outcome:
    out, rounds = serve_window(cell, seed, seconds, trace, clock, hooks,
                               control)
    check(out, cell, seed, rounds, control)
    return out


def serve_window(cell, seed: int, seconds: float, trace: bool, clock,
                 hooks=None, control: bool = False):
    """Set-up and the timed window: (outcome without checks, the served
    tokens [B, gen] of each round)."""
    from repro.distributed import ctx, sharding as sh
    from repro.launch.cells import activation_rules
    from repro.launch.mesh import make_host_mesh
    from repro.models import layers as L
    from repro.train import serve_step as ss
    from chipbench import spec

    hooks = hooks or {}
    cfg, tr = cell.config, cell.traffic
    ref_mod = spec.reference_module(cell)
    entry, mc = common.program_config(cfg)
    policy = L.Policy(compute_dtype=jnp.bfloat16)
    devices = jax.devices()[:cell.chips]
    mesh = make_host_mesh(devices=devices)
    b, plen, gen = tr["batch"], tr["prompt_len"], tr["gen"]
    max_len = plen + gen + 8
    prompts = ZipfSource(seed, b, plen, cfg["vocab_size"], tr["zipf_a"],
                         stream=PROMPTS)

    with mesh, ctx.activation_sharding(mesh, activation_rules(mc, mesh)):
        shapes = jax.eval_shape(lambda k: entry.module.init_params(k, mc),
                                jax.random.PRNGKey(0))
        common.check_shapes(shapes, ref_mod.backbone_shapes(cfg), "params")
        specs = sh.to_named(sh.tree_pspecs(shapes, mesh, sh.param_pspec),
                            mesh)
        tok_sharding = jax.NamedSharding(mesh, sh.batch_pspec((b, plen),
                                                              mesh))
        params = jax.jit(
            lambda k: weights.make_tree(k, "backbone", shapes, cfg["init"]),
            out_shardings=specs)(weights.seed_key(seed))
        prefill = jax.jit(ss.make_prefill_step(
            entry, mc, max_len=max_len, policy=policy,
            cache_dtype=jnp.bfloat16, logits_mode="last"))
        decode = jax.jit(ss.make_decode_step(entry, mc, policy=policy),
                         donate_argnums=1)
        decode = hooks.get("decode_step", lambda f: f)(decode)
        first_token = jax.jit(lambda lg: jnp.argmax(lg, -1)[:, None]
                              .astype(jnp.int32))

        def serve_round(r):
            """One round; returns (served tokens [B,gen], host arrival
            times of the tokens)."""
            with span("data"):
                toks = jax.device_put(prompts.batch(r)["tokens"],
                                      tok_sharding)
            with span("prefill"):
                out = prefill(params, toks, None)
                tok = first_token(out["next_token_logits"])
                cache = out["cache"]
                del out
            with span("token_fetch"):
                served = [np.asarray(tok)]
            times = [clock()]
            for _ in range(gen - 1):
                with span("decode"):
                    tok, cache = decode(params, cache, tok)
                with span("token_fetch"):
                    served.append(np.asarray(tok))
                times.append(clock())
            del cache
            return np.concatenate(served, 1), times

        # warm-up: the cell's one prefill shape and one decode shape
        warm = prefill(params, jax.device_put(prompts.batch(0)["tokens"],
                                              tok_sharding), None)
        tok, cache = decode(params, warm["cache"],
                            first_token(warm["next_token_logits"]))
        np.asarray(tok)
        del warm, cache, tok
        setup_s = clock()
        if control:
            toks = jax.device_put(prompts.batch(0)["tokens"], tok_sharding)
            cache = jax.eval_shape(prefill, params, toks, None)["cache"]
            memory = {"prefill": common.compiled_memory(prefill, params, toks,
                                                        None),
                      "decode": common.compiled_memory(
                          decode, params, cache,
                          jax.ShapeDtypeStruct((b, 1), jnp.int32))}

        tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(tdir)
        rounds, gaps, decode_bytes = [], [], 0.0
        with common.no_gc():
            t0 = clock()
            with span("window"):
                while True:
                    served, times = serve_round(len(rounds))
                    rounds.append(served)
                    gaps.extend(np.diff(times).tolist())
                    if clock() - t0 >= seconds:
                        break
            window_s = clock() - t0
        if trace:
            jax.profiler.stop_trace()
        peak = common.memory_peak_bytes(devices)
        del params

    n = len(rounds)
    for i in range(1, gen):
        decode_bytes += work.decode_step_bytes(cfg, b, plen + i)
    vocab = cfg["vocab_size"]
    failed = int(sum(np.sum(np.any((s < 0) | (s >= vocab), axis=1))
                     for s in rounds))
    out = common.Outcome(
        end_to_end={"serve_tokens_per_s": n * b * gen / window_s,
                    "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3,
                    "setup_s": setup_s, "peak_hbm_gib": peak / 2**30},
        counters={"rounds": n, "window_s": window_s,
                  "decode_steps": n * (gen - 1),
                  "prefill_flops": n * work.prefill_flops(cfg, b, plen),
                  "decode_bytes": n * decode_bytes},
        checks={}, attempted=n * b, failed=failed, memory_peak_bytes=peak,
        trace_dir=tdir)

    if control:
        out.counters["compiled_memory"] = memory
    return out, rounds


def check(out, cell, seed: int, rounds, control: bool = False) -> None:
    """Every finished round of the window against the reference."""
    from chipbench import spec
    cfg, tr = cell.config, cell.traffic
    ref_mod = spec.reference_module(cell)
    plen = tr["prompt_len"]
    prompts = ZipfSource(seed, tr["batch"], plen, cfg["vocab_size"],
                         tr["zipf_a"], stream=PROMPTS)
    seqs = [np.concatenate([prompts.batch(r)["tokens"], s], 1)
            for r, s in enumerate(rounds)]
    t_check = time.perf_counter()
    ref = ref_mod.Reference(cfg, seed, stored=jnp.float32)
    hidden = [ref.served_hidden(q, plen) for q in seqs]
    best, got = [], []
    for h, s in zip(hidden, rounds):
        b, g, _ = ref.logit_stats(h, s)
        best.append(np.asarray(b))
        got.append(np.asarray(g))
    stats = gap_stats(np.stack(best) - np.stack(got), np.stack(rounds))
    out.checks = {k: (stats[k], lim) for k, lim in cell.limits.items()}
    out.counters.update(gap_stats=stats, check_s=time.perf_counter() - t_check)
    if control:
        # at the same positions, the token the lower precision puts first,
        # read in the reference's logits
        low = ref_mod.Reference(cfg, seed, stored=jnp.float32, lowp="fp8")
        got_low = []
        for q, h, s in zip(seqs, hidden, rounds):
            _, _, pick = low.logit_stats(low.served_hidden(q, plen), s)
            got_low.append(np.asarray(ref.logit_stats(h, pick)[1]))
        out.counters["control"] = gap_stats(np.stack(best) - np.stack(got_low),
                                            np.stack(rounds))


def gap_stats(gaps, tokens, context: int = 4) -> dict:
    """How far below the reference's best logit the served tokens lie, over
    positions [round, sequence, token]: the widest gap, the mean gap, the
    share of tokens that are not the reference's first choice, and the mean
    gap over distinct contexts.  A context is a sequence's served token with
    the ``context`` served before it; greedy decoding of random weights
    falls into loops that repeat one near tie, and counted by context the
    loop counts once."""
    g = np.asarray(gaps, np.float64)
    by_context: dict = {}
    for r, b in np.ndindex(g.shape[:2]):
        row = tokens[r, b]
        for i in range(g.shape[2]):
            key = (r, b, row[max(0, i - context):i + 1].tobytes())
            by_context.setdefault(key, []).append(g[r, b, i])
    return {"logit_gap": float(g.max()), "logit_gap_mean": float(g.mean()),
            "mismatch_share": float(np.mean(g > 0)),
            "logit_gap_distinct": float(np.mean(
                [np.mean(v) for v in by_context.values()]))}
