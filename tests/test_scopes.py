"""Layer scopes in the step programs, ``op_layers``, and the runtime
counters of ``repro.obs`` (compiles, data waits) on the profiler's clock."""
import collections
import dataclasses
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.data.pipeline import Prefetcher
from repro.launch.cells import duplex_tcfg
from repro.models import layers as L, moe, registry
from repro.obs import runtime
from repro.obs.export import chrome_trace_events
from repro.obs.recorder import SpanRecorder
from repro.obs.scopes import LAYERS, OTHER, op_layers, scope_layer
from repro.train import serve_step as ss, train_step as ts

MOE = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
SERVE = ("embed", "attention", "kv_cache", "unembed_loss")
TRAIN = ("embed", "attention", "unembed_loss", "branch", "optimizer")
# the layers each program of a smoke preset runs
EXPECTED = {
    ("granite-moe-1b-a400m", "train"): TRAIN + MOE,
    ("granite-moe-1b-a400m", "prefill"): SERVE + MOE,
    ("granite-moe-1b-a400m", "decode"): SERVE + MOE,
    ("granite-3-8b", "train"): TRAIN + ("mlp",),
    ("granite-3-8b", "prefill"): SERVE + ("mlp",),
    ("granite-3-8b", "decode"): SERVE + ("mlp",),
    ("granite-4.0-h-small", "train"): TRAIN + MOE + ("mlp", "ssd"),
    ("granite-4.0-h-small", "prefill"): SERVE + MOE + ("mlp", "ssd"),
    ("granite-4.0-h-small", "decode"): SERVE + MOE + ("mlp", "ssd"),
}
# recurrent mixers, each in the forward of the preset that has it
MIXERS = {"mamba2-780m": "ssd", "recurrentgemma-9b": "lru"}


def step_programs(arch: str, s: int = 32, **overrides) -> dict:
    """{program: (jitted, args)} of a smoke preset, as the launchers
    build them, for shapes only: batch 2, ``s`` tokens a sequence, the
    preset's config with ``overrides``."""
    entry = registry.get(arch)
    cfg = dataclasses.replace(entry.config("smoke"), **overrides)
    policy = L.Policy(compute_dtype=jnp.bfloat16)
    tcfg = duplex_tcfg(cfg)
    state = jax.eval_shape(
        lambda k: ts.init_state(k, entry, cfg, tcfg, policy),
        jax.random.PRNGKey(0))
    b = 2
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
    prefill = jax.jit(ss.make_prefill_step(entry, cfg, max_len=s + 8,
                                           policy=policy, logits_mode="last"))
    cache = jax.eval_shape(prefill, state["backbone"], toks, None)["cache"]
    return {
        "train": (jax.jit(ts.make_train_step(entry, cfg, tcfg, policy)),
                  (state, {"tokens": toks, "labels": toks})),
        "prefill": (prefill, (state["backbone"], toks, None)),
        "decode": (jax.jit(ss.make_decode_step(entry, cfg, policy=policy)),
                   (state["backbone"], cache,
                    jax.ShapeDtypeStruct((b, 1), jnp.int32))),
    }


_LOWERED: dict = {}


def lowered(arch: str, program: str):
    if arch not in _LOWERED:
        _LOWERED[arch] = {name: fn.lower(*args) for name, (fn, args)
                          in step_programs(arch).items()}
    return _LOWERED[arch][program]


def scopes_in(lowered_text: str) -> set:
    """Layers named by the source locations of a lowered program."""
    return {scope_layer(loc)[0] for loc in
            re.findall(r'loc\("([^"]*)"', lowered_text)} - {OTHER}


def test_every_layer_is_checked_somewhere():
    checked = {layer for want in EXPECTED.values() for layer in want}
    assert checked | set(MIXERS.values()) == set(LAYERS)


@pytest.mark.parametrize("arch,program", sorted(EXPECTED))
def test_scopes_reach_the_program(arch, program):
    text = lowered(arch, program).as_text(debug_info=True)
    assert set(EXPECTED[arch, program]) <= scopes_in(text)


@pytest.mark.parametrize("arch,program", sorted(EXPECTED))
def test_every_contraction_maps_to_a_named_layer(arch, program):
    text = lowered(arch, program).compile().as_text()
    layers = op_layers(text)
    heavy = re.findall(r"^\s*(?:ROOT )?%(\S+) = [^\n]*? (?:dot|convolution)\(",
                       text, flags=re.M)
    assert heavy and all(layers[n][0] != OTHER for n in heavy)


@pytest.mark.parametrize("arch", sorted(MIXERS))
def test_recurrent_mixer_scope_in_forward(arch):
    entry = registry.get(arch)
    cfg = entry.config("smoke")
    params = jax.eval_shape(lambda k: entry.module.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = jax.jit(lambda p, t: entry.module.forward(p, cfg, t)["hidden"]) \
        .lower(params, toks).compile().as_text()
    assert MIXERS[arch] in {lp[0] for lp in op_layers(text).values()}


MOVES = re.compile(r"^\s*(?:ROOT )?%(\S+) = [^\n]*? (?:gather|scatter)\(",
                   re.M)
ROUTING = {"moe_router", "moe_dispatch", "moe_combine"}
_ROUTED: dict = {}


def routed(index_from: int) -> dict:
    """{program: (MoE routes recorded, compiled text)} of the smoke
    granite-moe preset in groups of ``moe._INDEX_ROUTE_MIN_GROUP`` tokens
    (train and prefill fill one group, decode's holds its batch of 2), with
    the index route taken from ``index_from`` tokens a group."""
    if index_from not in _ROUTED:
        n = moe._INDEX_ROUTE_MIN_GROUP
        out = {}
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe, "_INDEX_ROUTE_MIN_GROUP", index_from)
            t0 = time.time_ns()
            progs = step_programs("granite-moe-1b-a400m", s=n // 2,
                                  moe_group_size=n)
            for name, (fn, args) in progs.items():
                text = fn.lower(*args).compile().as_text()
                routes = [(r.name, r.args["group"])
                          for r in runtime.moe_routes_of(f"{name}_step")
                          if r.t0 >= t0]
                out[name] = routes, text
        _ROUTED[index_from] = out
    return _ROUTED[index_from]


@pytest.mark.parametrize("program,want", [
    ("train", "index"), ("prefill", "index"), ("decode", "onehot")])
def test_moe_route_by_group_size(program, want):
    n = moe._INDEX_ROUTE_MIN_GROUP
    routes, _ = routed(n)[program]
    assert routes and set(routes) == {(want, 2 if program == "decode" else n)}


def test_decode_moves_no_tokens_by_gather():
    _, text = routed(moe._INDEX_ROUTE_MIN_GROUP)["decode"]
    layers = op_layers(text)
    assert not [n for n in MOVES.findall(text)
                if layers[n][0] in ("moe_dispatch", "moe_combine")]


@pytest.mark.parametrize("program", ["train", "prefill"])
def test_index_route_gathers_map_to_routing_layers(program):
    counts = {}
    for index_from in (moe._INDEX_ROUTE_MIN_GROUP, 2**30):
        _, text = routed(index_from)[program]
        layers = op_layers(text)
        counts[index_from] = collections.Counter(
            layers[n][0] for n in MOVES.findall(text))
    new = counts[moe._INDEX_ROUTE_MIN_GROUP] - counts[2**30]
    assert new and set(new) <= ROUTING, new


# a module by hand: a layer loop whose weight cast the compiler hoisted and
# whose output it copied, both without metadata
LOOP_HLO = """HloModule jit_decode_step, is_scheduled=true

%fused_dot (p0: f32[4,4], p1: f32[4,4]) -> f32[4,4] {
  %p0 = f32[4,4]{1,0} parameter(0)
  %p1 = f32[4,4]{1,0} parameter(1)
  %dot.1 = f32[4,4]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(decode_step)/while/body/moe_experts/dot_general"}
  ROOT %add.1 = f32[4,4]{1,0} add(%dot.1, %p0), metadata={op_name="jit(decode_step)/while/body/add"}
}

%body (arg: (s32[], f32[4,4], f32[4,4])) -> (s32[], f32[4,4], f32[4,4]) {
  %arg = (s32[], f32[4,4]{1,0}, f32[4,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %wb = f32[4,4]{1,0} get-tuple-element(%arg), index=1
  %x = f32[4,4]{1,0} get-tuple-element(%arg), index=2
  %fusion.2 = f32[4,4]{1,0} fusion(%x, %wb), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(decode_step)/while/body/add"}
  %upd = f32[4,4]{1,0} add(%fusion.2, %x), metadata={op_name="jit(decode_step)/while/body/attention/kv_cache/dynamic_update_slice"}
  %grad = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/transpose(jvp(branch))/dot_general"}
  %ds = f32[4,4]{1,0} dynamic-slice(%wb, %i, %i), dynamic_slice_sizes={4,4}, metadata={op_name="jit(decode_step)/while/body/dynamic_slice"}
  %q = f32[4,4]{1,0} dot(%ds, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(decode_step)/while/body/attention/dot_general"}
  %norm = f32[4,4]{1,0} multiply(%q, %q), metadata={op_name="jit(decode_step)/while/body/mul"}
  ROOT %tuple.4 = (s32[], f32[4,4]{1,0}, f32[4,4]{1,0}) tuple(%i, %wb, %upd)
}

%cond (carg: (s32[], f32[4,4], f32[4,4])) -> pred[] {
  %carg = (s32[], f32[4,4]{1,0}, f32[4,4]{1,0}) parameter(0)
  %ci = s32[] get-tuple-element(%carg), index=0
  %lim = s32[] constant(3)
  ROOT %lt = pred[] compare(%ci, %lim), direction=LT
}

ENTRY %main (w: f32[4,4], h: f32[4,4]) -> f32[4,4] {
  %w = f32[4,4]{1,0} parameter(0), metadata={op_name="params['stack']['mlp']['wi']"}
  %h = f32[4,4]{1,0} parameter(1)
  %convert.5 = f32[4,4]{1,0} convert(%w)
  %zero = s32[] constant(0)
  %tuple.6 = (s32[], f32[4,4]{1,0}, f32[4,4]{1,0}) tuple(%zero, %convert.5, %h)
  %while.7 = (s32[], f32[4,4]{1,0}, f32[4,4]{1,0}) while(%tuple.6), condition=%cond, body=%body, metadata={op_name="jit(decode_step)/while"}
  %gte.8 = f32[4,4]{1,0} get-tuple-element(%while.7), index=2
  ROOT %copy.9 = f32[4,4]{1,0} copy(%gte.8)
}
"""


def test_op_layers_by_hand():
    got = op_layers(LOOP_HLO)
    # a fusion takes its contraction's layer over its own tail's
    assert got["fusion.2"] == ("moe_experts", "fwd")
    # the innermost scope wins; a gradient is its layer's backward
    assert got["upd"] == ("kv_cache", "fwd")
    assert got["grad"] == ("branch", "bwd")
    # a hoisted cast takes the layer of what reads it inside the loop
    assert got["convert.5"] == ("moe_experts", "fwd")
    # a copy of the loop's output takes the layer that made that element
    assert got["copy.9"] == ("kv_cache", "fwd")
    # the loop's slice of a layer's weights takes the layer that reads it;
    # other work under no scope stays unnamed
    assert got["ds"] == ("attention", "fwd")
    assert got["norm"] == (OTHER, "fwd")
    # an argument's pytree path names no scope
    assert got["w"][0] == OTHER and got["while.7"][0] == OTHER


def test_scope_layer_reads_the_path():
    assert scope_layer("jit(f)/attention/kv_cache/dus") == ("kv_cache", "fwd")
    assert scope_layer("jit(f)/transpose(jvp(unembed_loss))/dot_general") \
        == ("unembed_loss", "bwd")
    assert scope_layer("jit(f)/while/body/add") == (OTHER, "fwd")
    assert scope_layer("state['branch']['mlp']['wi']") == (OTHER, "fwd")


def _named(name: str):
    def fn(x):
        return jnp.sin(x) * 2.0
    fn.__name__ = fn.__qualname__ = name
    return fn


def test_a_second_install_records_nothing_twice():
    runtime.record_compiles()          # importing the step modules did once
    jax.jit(_named("install_probe_step"))(jnp.ones(2)).block_until_ready()
    assert runtime.backend_compiles("install_probe_step") == 1


def test_recorder_counts_first_compile_repeat_and_new_shape():
    step = jax.jit(_named("recorder_probe_step"))
    seen = []
    for n in (3, 3, 4):
        step(jnp.ones(n)).block_until_ready()
        seen.append(runtime.backend_compiles("recorder_probe_step"))
    assert seen == [1, 1, 2]
    kinds = {s.name for s in runtime.compiles.spans_of("compile")
             if "recorder_probe_step" in s.args["fun_name"]}
    assert kinds == {"trace", "lower", "backend"}


def _xplane(path) -> ProfileData:
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    return ProfileData.from_file(found[0])


def test_compile_span_lies_in_its_trace_annotation(tmp_path):
    step = jax.jit(_named("clock_probe_step"))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("compile_probe"):
            step(jnp.ones(5)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    prof = _xplane(tmp_path)
    start = next(dict(p.stats)["profile_start_time"] for p in prof.planes
                 if "profile_start_time" in dict(p.stats))
    (a, b), = [(start + int(e.start_ns), start + int(e.end_ns))
               for p in prof.planes if p.name == "/host:CPU"
               for line in p.lines for e in line.events
               if e.name == "compile_probe"]
    span, = [s for s in runtime.compiles.spans_of("compile")
             if s.name == "backend"
             and s.args["fun_name"] == "jit(clock_probe_step)"]
    assert a <= span.t0 < span.t1 <= b


class _SlowSource:
    def batch(self, i):
        time.sleep(0.01)
        return {"i": i}


def test_prefetcher_counts_waits_and_productions(monkeypatch):
    rec = SpanRecorder(maxlen=runtime.MAXLEN)
    monkeypatch.setattr(runtime, "data", rec)
    t0 = time.time_ns()
    feed = Prefetcher(_SlowSource(), depth=1)
    try:
        got = [feed.next()["i"] for _ in range(3)]
    finally:
        feed.close()
    assert got == [0, 1, 2]
    waits = rec.counter_samples("prefetch_wait_s")
    made = rec.counter_samples("prefetch_produce_s")
    assert len(waits) == 3 and all(c.value >= 0 for c in waits)
    assert waits[0].value > 0.005          # the first batch took 10 ms
    assert len(made) >= 3 and all(c.value >= 0.009 for c in made)
    assert all(t0 <= c.t <= time.time_ns() for c in waits + made)


def test_bounded_recorder_keeps_the_newest():
    rec = SpanRecorder(maxlen=2)
    for i in range(3):
        rec.counter("prefetch_wait_s", i, float(i))
        rec.span("compile", "backend", i, i + 1, fun_name=f"jit(f{i})")
    assert [c.value for c in rec.counters] == [1.0, 2.0]
    assert [s.args["fun_name"] for s in rec.spans] == ["jit(f1)", "jit(f2)"]
    names = {e.get("args", {}).get("name") for e in chrome_trace_events(rec)}
    assert "compiles" in names


@pytest.mark.parametrize("launcher,name", [("train", "train"),
                                           ("serve", "decode")])
def test_launchers_mark_each_step(monkeypatch, tmp_path, launcher, name):
    from repro.launch.serve import serve
    from repro.launch.train import train
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    argv = ["--arch", "granite-moe-1b-a400m", "--batch", "2"]
    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        if launcher == "train":
            train(argv + ["--steps", "2", "--seq", "16"])
        else:
            serve(argv + ["--prompt-len", "8", "--gen", "4"])
    finally:
        jax.profiler.stop_trace()
    steps = sorted(int(dict(e.stats)["step_num"])
                   for p in _xplane(trace_dir).planes if p.name == "/host:CPU"
                   for line in p.lines for e in line.events if e.name == name)
    assert steps == [0, 1]


@pytest.mark.parametrize("program", ["train", "prefill", "decode"])
def test_hybrid_records_its_ssd_plans_and_expert_share(program):
    """Each SSD mixer of a lowering records its chunk, heads, groups and
    largest per-chunk temporary; each MoE layer the experts it holds of
    the router's.  The hybrid smoke preset at batch 2 x 32: nine SSD
    layers and ten MoE layers a program."""
    cfg = registry.get("granite-4.0-h-small").config("smoke")
    t0 = time.time_ns()
    fn, args = step_programs("granite-4.0-h-small")[program]
    fn.lower(*args)
    name = f"{program}_step"
    plans = [r for r in runtime.ssd_plans_of(name) if r.t0 >= t0]
    q = 1 if program == "decode" else cfg.ssm_chunk
    heads = cfg.ssm_expand * cfg.d_model // cfg.ssm_headdim
    assert len(plans) == 9 and {r.name for r in plans} == {"ssd"}
    assert all(r.args == {"chunk": q, "heads": heads, "groups": 1,
                          "temp_bytes": 2 * heads * q * q * 4} for r in plans)
    routes = [r for r in runtime.moe_routes_of(name) if r.t0 >= t0]
    assert len(routes) == 10 and all(
        (r.args["experts"], r.args["routed"], r.args["offset"])
        == (cfg.n_experts, cfg.n_routed_experts, cfg.expert_offset)
        for r in routes)


def test_hybrid_ssd_temporaries_at_the_benchmark_size():
    """The benchmark's granite-4.0-h stage at 8 x 4096, lowered (not
    compiled): every SSD mixer's largest per-chunk temporary is one 256
    -token chunk's [8, 128, 256, 256] float32, 256 MiB, where building all
    16 chunks' decays at once took 4 GiB."""
    cfg = dataclasses.replace(
        registry.get("granite-4.0-h-small").config("full"), n_layers=10,
        n_experts=9, n_routed_experts=72, vocab=12_544)
    entry = registry.get("granite-4.0-h-small")
    policy = L.Policy(compute_dtype=jnp.bfloat16)
    tcfg = duplex_tcfg(cfg)
    state = jax.eval_shape(
        lambda k: ts.init_state(k, entry, cfg, tcfg, policy),
        jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((8, 4096), jnp.int32)
    t0 = time.time_ns()
    jax.jit(ts.make_train_step(entry, cfg, tcfg, policy)).lower(
        state, {"tokens": toks, "labels": toks})
    plans = [r.args for r in runtime.ssd_plans_of("train_step")
             if r.t0 >= t0]
    assert plans == [{"chunk": 256, "heads": 128, "groups": 1,
                      "temp_bytes": 8 * 128 * 256 * 256 * 4}] * 9
    assert tcfg.duplex.n_blocks == 8
