"""Ahead-of-time compiles for a described TPU v5e chip, no chip attached.

The chip's compiler refuses what interpret mode accepts (unaligned shape
casts, blocks that break the (8, 128) tiling rule, programs that do not fit
HBM), so the kernels and one whole step are compiled here at real widths.

The topology is described inside a fixture, never at import: describing it
loads the TPU library, which one process at a time may hold.
"""
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.common import ShapeSpec
from repro.distributed import ctx
from repro.kernels.bfp_matmul import bfp_matmul
from repro.kernels.bfp_quant import bfp_matmul_packed, bfp_quantize_pallas
from repro.kernels.flash_attention import flash_attention
from repro.launch.cells import activation_rules, build_cell
from repro.launch.mesh import make_host_mesh
from repro.obs import runtime
from repro.obs.scopes import OTHER, op_layers, scope_layer
from test_scopes import EXPECTED, step_programs

HBM_BYTES = 16 * 2**30          # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a TPU executable written to the persistent cache cannot be read
        # back without a chip; keep these compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_at_granite_widths(one_chip):
    q = _sds((1, 16, 4096, 64), jnp.bfloat16, one_chip)
    kv = _sds((1, 8, 4096, 64), jnp.bfloat16, one_chip)
    _assert_kernel(lambda q, k, v: flash_attention(q, k, v), q, kv, kv)


# (4096, 1024) x (1024, 512): a granite-width activation times a weight;
# 256: the duplex branch width (launch.cells.duplex_tcfg)
@pytest.mark.parametrize("m,k,n", [(4096, 1024, 512), (256, 256, 256)])
def test_bfp_kernels_compile(one_chip, m, k, n):
    g = 32
    f32, i8 = jnp.float32, jnp.int8
    _assert_kernel(lambda a, b: bfp_matmul(a, b, group=g),
                   _sds((m, k), f32, one_chip), _sds((k, n), f32, one_chip))
    _assert_kernel(lambda a: bfp_quantize_pallas(a, group=g),
                   _sds((m, k), f32, one_chip))
    _assert_kernel(
        lambda am, ae, bm, be: bfp_matmul_packed(am, ae, bm, be, group=g),
        _sds((m, k), i8, one_chip), _sds((m // g, k // g), i8, one_chip),
        _sds((k, n), i8, one_chip), _sds((k // g, n // g), i8, one_chip))


def test_granite_decode_step_fits_one_chip(topo):
    mesh = make_host_mesh(devices=topo.devices[:1])
    shape = ShapeSpec("decode_4k", 4096, 8, "decode")
    fn, args, in_sh, out_sh, donate, cfg, _ = build_cell(
        "granite-moe-1b-a400m", shape, mesh)
    with mesh, ctx.activation_sharding(mesh, activation_rules(cfg, mesh)):
        compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used


def test_granite_train_step_routes_by_index_and_fits_one_chip(topo):
    mesh = make_host_mesh(devices=topo.devices[:1])
    shape = ShapeSpec("train_8x4k", 4096, 8, "train")
    t0 = time.time_ns()
    fn, args, in_sh, out_sh, donate, cfg, _ = build_cell(
        "granite-moe-1b-a400m", shape, mesh)
    with mesh, ctx.activation_sharding(mesh, activation_rules(cfg, mesh)):
        compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate).lower(*args).compile()
    routes = {(r.name, r.args["group"])
              for r in runtime.moe_routes_of("train_step") if r.t0 >= t0}
    assert routes == {("index", 4096)}
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used


@pytest.mark.parametrize("arch,program", sorted(EXPECTED))
def test_layer_scopes_survive_the_chip_compiler(one_chip, arch, program):
    fn, args = step_programs(arch)[program]
    args = jax.tree_util.tree_map(lambda x: _sds(x.shape, x.dtype, one_chip),
                                  args)
    text = fn.lower(*args).compile().as_text()
    named = {scope_layer(m)[0] for m in re.findall(r'op_name="([^"]*)"', text)}
    assert set(EXPECTED[arch, program]) <= named
    layers = op_layers(text)
    heavy = re.findall(r"^\s*(?:ROOT )?%(\S+) = [^\n]*? (?:dot|convolution)\(",
                       text, flags=re.M)
    assert heavy and all(layers[n][0] != OTHER for n in heavy)
