"""Faults planted in the program under test, to show that ``correct``
catches them.  Each is a context manager that patches one piece of the
timed path of a traffic kind; the benchmark's own runs never use them.

* ``unchanged_state``: the step returns the state it was given (the fleet
  does not move its walkers; the trainer's step leaves params and
  moments as they were).
* ``half_batch``: half of the batch left out and the mean taken over the
  rest (the committee statistics over half of the members; the loss over
  half of each member's minibatch).
* ``altered_answer``: one answer altered where it is produced (walker 0's
  proposal moved by 0.01; the loss the train step reports scaled by 1.1).
* ``exchange_left_out`` (a fleet on a mesh only): the exchange between
  chips left out, the budget controller steered by the first chip's
  rows alone instead of the rate over all chips.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import jax.numpy as jnp

FAULTS = ("unchanged_state", "half_batch", "altered_answer")
MESH_FAULTS = ("exchange_left_out",)


@contextlib.contextmanager
def planted(name: str, loop: str, chips: int = 1):
    if name not in FAULTS + MESH_FAULTS:
        raise KeyError(f"unknown fault {name!r}; known: "
                       f"{FAULTS + MESH_FAULTS}")
    make = globals()[f"_{loop}_{name}"]
    with (make(chips) if name in MESH_FAULTS else make()):
        yield


# ------------------------------------------------------------- exchange
def _exchange_unchanged_state():
    from repro.exploration.fleet import WalkerFleet

    def step_fn(self, carry):
        return carry["x"], dict(carry)

    return mock.patch.object(WalkerFleet, "_step_fn", step_fn)


def _exchange_half_batch():
    from repro.core.acquisition import FusedEngine

    orig = FusedEngine._committee_uq

    def uq(self, preds, nb):
        return orig(self, preds[: max(1, preds.shape[0] // 2)], nb)

    return mock.patch.object(FusedEngine, "_committee_uq", uq)


def _exchange_altered_answer():
    from repro.exploration.fleet import WalkerFleet

    orig = WalkerFleet._step_fn

    def step_fn(self, carry):
        x, mid = orig(self, carry)
        x = x.at[0, 0].add(0.01)
        return x, dict(mid, x=x)

    return mock.patch.object(WalkerFleet, "_step_fn", step_fn)


def _exchange_exchange_left_out(chips: int):
    from repro.core.budget import BudgetRule

    def apply_stateful(self, stats, mask, state):
        sel = mask & (stats.scalar_std > state["threshold"])
        rows = sel.shape[0] // chips
        n = jnp.maximum(jnp.asarray(stats.n_valid, jnp.int32) // chips, 1)
        rate = jnp.sum(sel[:rows]).astype(jnp.float32) / n.astype(
            jnp.float32)
        lo, hi = self._bounds()
        return stats, sel, self.controller.update(state, rate, lo, hi)

    return mock.patch.object(BudgetRule, "apply_stateful", apply_stateful)


# ---------------------------------------------------------------- train
def _train_unchanged_state():
    from repro.training import committee_trainer as ct

    orig = ct.make_train_step

    def make(loss_fn, cfg):
        step = orig(loss_fn, cfg)

        def frozen(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return frozen

    return mock.patch.object(ct, "make_train_step", make)


def _train_half_batch():
    from repro.training.committee_trainer import CommitteeTrainer

    orig = CommitteeTrainer._draw_indices

    def draw(self, key, size):
        idx = orig(self, key, size)
        return idx[:, : max(1, idx.shape[1] // 2)]

    return mock.patch.object(CommitteeTrainer, "_draw_indices", draw)


def _train_altered_answer():
    from repro.training import committee_trainer as ct

    orig = ct.make_train_step

    def make(loss_fn, cfg):
        step = orig(loss_fn, cfg)

        def altered(state, batch):
            new, metrics = step(state, batch)
            return new, dict(metrics, loss=metrics["loss"] * jnp.float32(1.1))
        return altered

    return mock.patch.object(ct, "make_train_step", make)
