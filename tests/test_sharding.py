"""Sharding rules: per-arch PartitionSpecs, divisibility guards, variants."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as sh
from repro.models import registry

# The rules consult only mesh.shape / axis_names, so an AbstractMesh stands
# in for the 256/512-device production meshes without touching device state
# (the real meshes are exercised by launch/dryrun.py).


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def multipod():
    return jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_attention_weights_tp(mesh):
    assert sh.param_pspec("stack/sub0/attn/wq/w", (80, 8192, 8192), mesh) == \
        P(None, "data", "model")
    assert sh.param_pspec("stack/sub0/attn/wo/w", (80, 8192, 8192), mesh) == \
        P(None, "model", "data")
    assert sh.param_pspec("rem/sub0/attn/wq/w", (4096, 4096), mesh) == \
        P("data", "model")


def test_divisibility_guard_drops_axis(mesh):
    # 36-head starcoder bias: 4608 % 16 == 0 → sharded; 13 → replicated
    assert sh.param_pspec("attn/wq/b", (4608,), mesh) == P("model")
    assert sh.param_pspec("attn/wq/b", (13,), mesh) == P(None)


def test_moe_expert_parallel(mesh):
    spec = sh.param_pspec("stack/sub0/moe/wi", (48, 128, 5120, 8192), mesh)
    assert spec == P(None, "model", "data", None)
    assert sh.param_pspec("stack/sub0/moe/router/w", (48, 5120, 128),
                          mesh) == P(None, None, None)


def test_embed_fsdp_tp(mesh):
    assert sh.param_pspec("embed/table", (152064, 8192), mesh) == \
        P("model", "data")


def test_norms_replicated(mesh):
    assert sh.param_pspec("stack/sub0/norm/scale", (80, 8192), mesh) == \
        P(None, None)
    # but the SSD inner norm spans the model-sharded d_inner
    assert sh.param_pspec("stack/sub0/ssd/norm/scale", (48, 3072), mesh) == \
        P(None, "model")


def test_fsdp_pure_variant(mesh):
    # dim0 divisible by 256 → fully sharded over (data, model)
    assert sh.param_pspec("stack/sub0/attn/wq/w", (80, 8192, 8192), mesh,
                          fsdp_pure=True) == P(None, ("data", "model"), None)
    # 29568 % 256 != 0 → the other dim (8192) carries the full 256-way shard
    spec = sh.param_pspec("stack/sub0/mlp/wo/w", (80, 29568, 8192), mesh,
                          fsdp_pure=True)
    shards = 1
    for ax in spec:
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            if a is not None:
                shards *= mesh.shape[a]
    assert shards == 256, spec


def test_lru_gate_variants(mesh):
    assert sh.param_pspec("stack/sub0/lru/wr/w", (12, 4096, 4096), mesh) == \
        P(None, "model", None)
    assert sh.param_pspec("stack/sub0/lru/wr/w", (12, 4096, 4096), mesh,
                          lru_gates_colparallel=True) == \
        P(None, None, "model")


def test_batch_specs(mesh, multipod):
    assert sh.batch_pspec((256, 4096), mesh) == P("data", None)
    assert sh.batch_pspec((256, 4096), multipod) == P(("pod", "data"), None)
    # batch 1 (long_500k): nothing divides → replicated
    assert sh.batch_pspec((1, 1), mesh) == P(None, None)
    # fsdp_pure: batch over every axis
    assert sh.batch_pspec((256, 4096), mesh, include_model=True) == \
        P(("data", "model"), None)


def test_cache_specs(mesh):
    # stacked KV cache: [n_rep, B, S, KV, hd] — seq over model, batch DP
    assert sh.cache_pspec("stack/sub0/k", (80, 128, 32768, 8, 128), mesh) == \
        P(None, "data", "model", None, None)
    # ring cache position array replicated; len scalar
    assert sh.cache_pspec("stack/sub0/pos", (12, 2048), mesh) == P(None, None)
    assert sh.cache_pspec("stack/sub0/len", (12,), mesh) == P()
    # ssd state: heads over model
    assert sh.cache_pspec("stack/sub0/h", (48, 128, 48, 64, 128), mesh) == \
        P(None, "data", "model", None, None)


def test_optimizer_state_mirrors_params(mesh):
    state_path = "opt/mu/branch/blocks/f1/attn/wq/w"
    assert sh._strip(state_path) == "blocks/f1/attn/wq/w"
    assert sh.param_pspec(sh._strip(state_path), (8, 1024, 1024), mesh) == \
        P(None, "data", "model")


def test_every_arch_params_get_specs(mesh):
    """No param of any full config falls through with a bad spec rank."""
    import jax.numpy as jnp
    for name in registry.ARCHS:
        entry = registry.get(name)
        shapes = jax.eval_shape(
            lambda k: entry.module.init_params(k, entry.full),
            jax.random.PRNGKey(0))
        specs = sh.tree_pspecs(shapes, mesh, sh.param_pspec)
        flat_s, _ = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_x = jax.tree_util.tree_leaves(shapes)
        assert len(flat_s) == len(flat_x)
        for x, s in zip(flat_x, flat_s):
            assert len(s) <= len(x.shape), (name, x.shape, s)
            for dim, ax in zip(x.shape, s):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axes:
                    n *= mesh.shape[a]
                assert dim % n == 0, (name, x.shape, s)
