"""granite-4.0-h's plain reference (``chipbench/reference/granite_hybrid.py``)
against the program at the smoke widths on the CPU, the program's
configuration against the files, and a whole harness run of a tiny hybrid
cell."""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common, spec, weights
from chipbench.reference import granite_hybrid as H
from chipbench.tokens import ZipfSource
from test_chipbench_reference import _program_train

DATA = Path(__file__).resolve().parent / "data"
REPO = Path(__file__).resolve().parents[2]
FILES = {"tiny-hybrid": DATA / "configs" / "tiny-hybrid.json",
         "granite-4.0-h-small-ep8":
         REPO / "chipbench" / "configs" / "granite-4.0-h-small-ep8.json"}
KIND = {"ssd": H.MAMBA, "attn": H.ATTENTION}


def _cfg(name="tiny-hybrid"):
    return json.loads(FILES[name].read_text())


@pytest.mark.parametrize("name", sorted(FILES))
def test_program_config_is_the_files(name):
    """What ``common.program_config`` does not compare: the layer kinds,
    the SSD's sizes, the expert share and granite's multipliers."""
    cfg = _cfg(name)
    entry, mc = common.program_config(cfg)
    got = {"layers": [KIND[s.kind] for s in mc.pattern] * mc.n_rep,
           "heads": mc.ssm_expand * mc.d_model // mc.ssm_headdim,
           "head": mc.ssm_headdim, "state": mc.ssm_state,
           "expand": mc.ssm_expand, "chunk": mc.ssm_chunk,
           "conv": mc.conv_width, "routed": mc.n_routed_experts,
           "first": mc.expert_offset, "shared": mc.shared_d_ff,
           "embed": mc.embed_mult, "attn": mc.attn_mult,
           "resid": mc.resid_mult, "logits": mc.logits_div,
           "eps": mc.norm_eps, "rope": mc.pos_embed}
    want = {"layers": H.layer_kinds(cfg), "heads": cfg["mamba_n_heads"],
            "head": cfg["mamba_d_head"], "state": cfg["mamba_d_state"],
            "expand": cfg["mamba_expand"], "chunk": cfg["mamba_chunk_size"],
            "conv": cfg["mamba_d_conv"], "routed": cfg["router_experts"],
            "first": cfg["first_expert"],
            "shared": cfg["shared_intermediate_size"],
            "embed": cfg["embedding_multiplier"],
            "attn": cfg["attention_multiplier"],
            "resid": cfg["residual_multiplier"],
            "logits": cfg["logits_scaling"], "eps": cfg["rms_norm_eps"],
            "rope": "none"}
    assert got == want
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["mamba_n_groups"] == 1 and mc.shared_expert
    params = jax.eval_shape(lambda k: entry.module.init_params(k, mc),
                            jax.random.PRNGKey(0))
    common.check_shapes(params, H.backbone_shapes(cfg), "backbone")


def _program_forward(cfg, seed, tokens, tap_indices):
    from repro.models import layers as L
    entry, mc = common.program_config(cfg)
    shapes = jax.eval_shape(lambda k: entry.module.init_params(k, mc),
                            jax.random.PRNGKey(0))
    params = weights.make_tree(weights.seed_key(seed), "backbone", shapes,
                               cfg["init"])
    pool = cfg["duplex"]["pool_factor"]
    return jax.jit(lambda p, t: entry.module.forward(
        p, mc, t, policy=L.Policy(compute_dtype=jnp.float32),
        collect_taps=True, tap_indices=tap_indices, tap_pool=pool))(
            params, jnp.asarray(tokens))


def test_forward_and_taps_agree():
    """Embedding, final hidden state and the taps of the layers the
    reference taps, in float32 on both sides: the chunked SSD against the
    token-by-token recurrence, the expert share, the multipliers."""
    from repro.train.train_step import tap_indices
    cfg = _cfg()
    seed = 2**33 + 17
    tokens = ZipfSource(seed, 2, 64, cfg["vocab_size"]).batch(0)["tokens"]
    idx = tap_indices(cfg["num_hidden_layers"], cfg["duplex"]["n_blocks"])
    prog = _program_forward(cfg, seed, tokens, idx)
    with jax.default_matmul_precision("highest"):
        (emb, hidden, taps), = H.Reference(cfg, seed).forward([tokens],
                                                                taps=idx)
    np.testing.assert_allclose(prog["emb"], emb, rtol=1e-6)
    # hidden states of order 1 after ten layers
    np.testing.assert_allclose(prog["hidden"], hidden, atol=2e-4)
    np.testing.assert_allclose(prog["taps"], taps, atol=2e-4)


def test_taps_land_on_the_reference_layers():
    """The duplex branch's 8 taps over one period of 10 layers are the
    layers ``tap_indices(10, 8)`` names: rows of the taps of every layer."""
    from repro.train.train_step import tap_indices, tapped_layers
    cfg = _cfg()
    _, mc = common.program_config(cfg)
    idx = tap_indices(tapped_layers(mc), cfg["duplex"]["n_blocks"])
    assert mc.n_rep == 1 and list(idx) == [0, 1, 3, 4, 5, 6, 8, 9]
    tokens = ZipfSource(5, 2, 64, cfg["vocab_size"]).batch(0)["tokens"]
    every = _program_forward(cfg, 5, tokens, np.arange(10))["taps"]
    got = _program_forward(cfg, 5, tokens, idx)["taps"]
    np.testing.assert_array_equal(got, every[idx])
    assert all(float(jnp.max(jnp.abs(every[i] - every[i + 1]))) > 0
               for i in range(9))


def test_train_steps_agree():
    cfg = _cfg()
    seed = 31337
    batches = [ZipfSource(seed, 2, 64, cfg["vocab_size"]).batch(i)
               for i in range(3)]
    prog = _program_train(cfg, seed, batches)
    ref = H.Reference(cfg, seed).train(batches, 3)
    # float32 on both sides; the chunked scan sums in another order than
    # the recurrence, which moves a gradient's norm by a few parts in 1e5
    np.testing.assert_allclose(prog["losses"], ref["losses"], rtol=1e-5)
    assert common.worst_leaf_gap(prog["grad"], ref["grad"]) < 1e-3
    assert common.worst_leaf_gap(prog["change"], ref["change"]) < 1e-3


def test_zeroed_carry_fails_the_loss():
    """A state not carried across chunks of ``mamba_chunk_size`` tokens, in
    the reference's place, moves the loss past the tiny cell's limit."""
    cfg = _cfg()
    seed, chunk = 4242, cfg["mamba_chunk_size"]
    limit = json.loads((DATA / "workloads" / "tiny-hybrid-train.json")
                       .read_text())["limits"]["loss"]
    batches = [ZipfSource(seed, 2, 64, cfg["vocab_size"]).batch(0)]
    whole = H.Reference(cfg, seed).train(batches, 1)["losses"][0]
    recurrence = H.ssd_recurrence

    def cut(x, dt, A, B, C):
        parts = [recurrence(*(a[:, i:i + chunk] for a in (x, dt)), A,
                            *(a[:, i:i + chunk] for a in (B, C)))
                 for i in range(0, x.shape[1], chunk)]
        return jnp.concatenate(parts, 1)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(H, "ssd_recurrence", cut)
        zeroed = H.Reference(cfg, seed).train(batches, 1)["losses"][0]
    assert abs(zeroed - whole) / whole > 2 * limit


def _hybrid_bench(bench_copy) -> tuple[Path, Path]:
    root, path = bench_copy
    bench = json.loads(path.read_text())
    bench["workloads"].append(
        {"name": "tiny-hybrid-train", "config": "tiny-hybrid",
         "traffic": "tiny-train", "chips": 1,
         "why": "hybrid duplex step at smoke widths"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "g4h-small-train-8x4096" in m.get("workloads", ()):
            m["workloads"].append("tiny-hybrid-train")
    path.write_text(json.dumps(bench, indent=1))
    return root, path


def test_harness_runs_a_hybrid_cell(bench_copy, no_compile_cache, capsys):
    """A whole traced run of the tiny hybrid cell: ``correct`` from the
    hybrid reference, and ``hybrid_step_mfu.train`` in the line."""
    from chipbench import run
    root, path = _hybrid_bench(bench_copy)
    rc = run.main(["--workload", "tiny-hybrid-train", "--seed",
                   str(2**32 + 99), "--seconds", "0.5", "--trace", "1"],
                  look_for_chip=False, root=root, bench_path=path)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["metrics"]["hybrid_step_mfu.train"]["value"] > 0


def _ctx(cell, **counters):
    return {"cell": cell, "counters": counters,
            "peaks": {"bf16_flops": 197e12}}


def test_hybrid_step_mfu_reads_a_recorded_context():
    from chipbench import work_hybrid
    bench = spec.load_json(REPO / "BENCHMARK.json")
    read = spec.metric_reader(spec.ROOT, "hybrid_step_mfu.train")
    cell = spec.load_cell(bench, "g4h-small-train-8x4096")
    assert "hybrid_step_mfu.train" in {m["name"] for m in cell.per_layer}
    flops = work_hybrid.train_step_flops(cell.config, cell.config["duplex"],
                                         8, 4096)
    got = read(_ctx(cell, steps=11, window_s=30.5))
    assert got == pytest.approx(100 * 11 * flops / 30.5 / 197e12)
    assert read(_ctx(cell, steps=0, window_s=30.5)) is None
    dense = spec.load_cell(bench, "g8b-stage-train-8x4096")
    assert read(_ctx(dense, steps=11, window_s=30.5)) is None


def test_control_fails_where_the_program_passes(bench_copy,
                                                no_compile_cache):
    """The hybrid's control (float8 where the program holds bfloat16: the
    matmul operands, the SSD's x, B and C, attention's q, k and v, and the
    residual stream) fails a limit of the tiny cell that the program meets,
    reading three times the program's number."""
    root, path = _hybrid_bench(bench_copy)
    c = spec.load_cell(spec.load_json(path), "tiny-hybrid-train", root)
    out = spec.job_module(c).run(c, 4242, 0.0, False,
                                 common.Clock(time.perf_counter()),
                                 control=True)
    assert out.correct, out.checks
    control = out.counters["control"]
    assert any(control[k] > lim and control[k] > 3 * v
               for k, (v, lim) in out.checks.items())
