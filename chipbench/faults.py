"""Faults planted under the timed path, to show that ``correct`` catches
them.  Each wraps one of the program's jitted steps.  Used by the tests
and by ``chipbench.calibrate``; the benchmark's runs never plant one."""
from __future__ import annotations

import jax


def state_unchanged(step):
    """A train step that computes but returns the state it was given."""
    return jax.jit(lambda st, bt: (st, step(st, bt)[1]), donate_argnums=0)


def half_batch(step):
    """A train step that leaves out half of the batch and takes the mean
    over the rest."""
    return jax.jit(lambda st, bt: step(st, jax.tree_util.tree_map(
        lambda x: x[:x.shape[0] // 2], bt)), donate_argnums=0)


def altered_token(vocab: int):
    """A decode step whose token is altered where it is produced."""
    def wrap(step):
        def f(params, cache, tok):
            nxt, cache = step(params, cache, tok)
            return (nxt + 1) % vocab, cache
        return jax.jit(f, donate_argnums=1)
    return wrap


def hooks(name: str, cell) -> dict:
    if name == "state_unchanged":
        return {"train_step": state_unchanged}
    if name == "half_batch":
        return {"train_step": half_batch}
    if name == "altered_token":
        return {"decode_step": altered_token(cell.config["vocab_size"])}
    raise KeyError(name)
