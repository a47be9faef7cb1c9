"""Checkpointing (atomic, keep-k, integrity, elastic reshard), data pipeline
determinism, gradient compression, loop resume."""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import Checkpointer, CheckpointConfig
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM, make_source
from repro.optim import compress


def _state(key=0):
    k = jax.random.PRNGKey(key)
    return {
        "step": jnp.asarray(7, jnp.int32),
        "branch": {"w": jax.random.normal(k, (16, 32)),
                   "b": jnp.zeros((32,))},
        "opt": {"mu": {"w": jnp.ones((16, 32)) * 0.5,
                       "b": jnp.zeros((32,))}},
    }


def test_save_restore_roundtrip(tmp_path):
    ck = Checkpointer(CheckpointConfig(str(tmp_path)))
    state = _state()
    ck.save(7, state)
    out = ck.restore()
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_k_gc(tmp_path):
    ck = Checkpointer(CheckpointConfig(str(tmp_path), keep=2))
    for s in (1, 2, 3, 4):
        ck.save(s, _state())
    assert ck.all_steps() == [3, 4]


def test_async_save_then_restore(tmp_path):
    ck = Checkpointer(CheckpointConfig(str(tmp_path)))
    ck.save(5, _state(), blocking=False)
    ck.wait()
    assert ck.latest_step() == 5


def test_corrupt_blob_detected(tmp_path):
    ck = Checkpointer(CheckpointConfig(str(tmp_path)))
    ck.save(1, _state())
    d = next(Path(tmp_path).glob("step_*"))
    victim = next(d.glob("arr_*.bin"))
    victim.write_bytes(b"corrupted!")
    with pytest.raises(IOError, match="checksum"):
        ck.restore()


def test_unpublished_tmp_ignored(tmp_path):
    ck = Checkpointer(CheckpointConfig(str(tmp_path)))
    ck.save(1, _state())
    (Path(tmp_path) / "step_000000000009.tmp").mkdir()
    assert ck.latest_step() == 1


def test_elastic_restore_new_sharding(tmp_path):
    """Restore re-shards onto a different mesh than the save ran under."""
    ck = Checkpointer(CheckpointConfig(str(tmp_path)))
    state = _state()
    ck.save(3, state)
    mesh = jax.make_mesh((1,), ("data",))
    shardings = jax.tree_util.tree_map(
        lambda x: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        state)
    out = ck.restore(shardings=shardings)
    assert out["branch"]["w"].sharding.mesh.shape == {"data": 1}
    np.testing.assert_allclose(np.asarray(out["branch"]["w"]),
                               np.asarray(state["branch"]["w"]))


# ----------------------------- data ----------------------------------------

def test_data_deterministic_and_restart_consistent():
    cfg = DataConfig(vocab=100, seq_len=32, batch_per_host=4, seed=3)
    src = SyntheticLM(cfg)
    b5a = src.batch(5)
    b5b = SyntheticLM(cfg).batch(5)     # fresh instance = restart
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    assert not np.array_equal(src.batch(6)["tokens"], b5a["tokens"])


def test_data_host_sharding_distinct():
    cfg = DataConfig(vocab=100, seq_len=16, batch_per_host=2)
    a = SyntheticLM(cfg, host_id=0).batch(0)
    b = SyntheticLM(cfg, host_id=1).batch(0)
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_labels_are_shifted_tokens():
    cfg = DataConfig(vocab=50, seq_len=16, batch_per_host=2)
    b = SyntheticLM(cfg).batch(0)
    assert b["tokens"].shape == b["labels"].shape == (2, 16)
    assert b["tokens"].max() < 50 and b["tokens"].min() >= 0


def test_prefetcher_orders_batches():
    cfg = DataConfig(vocab=64, seq_len=8, batch_per_host=1)
    src = SyntheticLM(cfg)
    pf = Prefetcher(src, start_index=3)
    try:
        np.testing.assert_array_equal(pf.next()["tokens"],
                                      src.batch(3)["tokens"])
        np.testing.assert_array_equal(pf.next()["tokens"],
                                      src.batch(4)["tokens"])
    finally:
        pf.close()


def test_byte_corpus(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_bytes(b"the quick brown fox jumps over the lazy dog " * 50)
    cfg = DataConfig(vocab=256, seq_len=16, batch_per_host=2, kind="bytes",
                     path=str(p))
    b = make_source(cfg).batch(0)
    assert b["tokens"].shape == (2, 16)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


# ----------------------------- compression ---------------------------------

def test_compression_roundtrip_error_small():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3
    y = compress.compress_decompress(x)
    rel = float(jnp.linalg.norm(x - y) / jnp.linalg.norm(x))
    assert rel < 0.01


def test_error_feedback_carries_residual():
    g = {"w": jax.random.normal(jax.random.PRNGKey(1), (64,))}
    r0 = {"w": jnp.zeros((64,))}
    sent, r1 = compress.error_feedback_update(g, r0)
    np.testing.assert_allclose(np.asarray(sent["w"] + r1["w"]),
                               np.asarray(g["w"]), rtol=1e-5, atol=1e-6)
    # residual feeds the next round: cumulative sent converges to cumulative g
    sent2, r2 = compress.error_feedback_update(g, r1)
    total_sent = np.asarray(sent["w"] + sent2["w"])
    np.testing.assert_allclose(total_sent + np.asarray(r2["w"]),
                               2 * np.asarray(g["w"]), rtol=1e-5, atol=1e-5)


def test_compressed_psum_matches_mean():
    """Under shard_map over a 1-device axis, compressed psum ≈ identity."""
    mesh = jax.make_mesh((1,), ("d",))
    x = jax.random.normal(jax.random.PRNGKey(2), (128,))

    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(lambda v: compress.compressed_psum(v, "d"),
                      mesh=mesh, in_specs=P(), out_specs=P())
    y = f(x)
    rel = float(jnp.linalg.norm(x - y) / jnp.linalg.norm(x))
    assert rel < 0.01
