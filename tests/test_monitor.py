"""``Monitor.span``: timer totals and self time, per-thread span stacks,
and the names the program puts into a ``jax.profiler`` trace and into its
device programs.

The fleet and the trainer here run the program's own potential
(``models/potential.py``) at a tiny size, so the named scopes of the
model, the fused step-and-score program and the train step all appear.
"""
import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.pal_potential import PotentialConfig
from repro.core import acquisition as acq
from repro.core.buffers import OracleInputBuffer
from repro.core.controller import Exchange, ExchangeConfig, PredictionPool
from repro.core.monitor import SPAN_NAMES, Monitor
from repro.exploration.fleet import FleetConfig, WalkerFleet
from repro.models import potential as pot
from repro.training.committee_trainer import CommitteeTrainer

CFG = PotentialConfig(n_atoms=4, committee_size=3, hidden=(8,), n_rbf=6,
                      r_cut=3.0)
D = 3 * CFG.n_atoms

FLEET_SPANS = ("exchange.round", "exchange.refresh_weights",
               "exchange.predict", "engine.dispatch", "engine.wait",
               "engine.fetch_selected", "exchange.oracle_put",
               "exchange.min_interval")
TRAIN_SPANS = ("trainer.round", "trainer.dispatch", "trainer.sync")


def _forces(p, flat_batch):
    def one(flat):
        _, f = pot.energy_forces(p, flat.reshape(CFG.n_atoms, 3), CFG)
        return f.reshape(-1)
    return jax.vmap(one)(flat_batch)


def _loss(p, batch):
    return jnp.mean((_forces(p, batch["x"]) - batch["y"]) ** 2), {}


def _x0(n, seed=0):
    rng = np.random.RandomState(seed)
    grid = np.array([[0, 0, 0], [1.3, 0, 0], [0, 1.3, 0], [0, 0, 1.3]])
    return (grid[None] + 0.05 * rng.randn(n, CFG.n_atoms, 3)).reshape(
        n, D).astype(np.float32)


def _exchange(mon, min_interval=0.0):
    """A 4-walker fleet behind an Exchange whose threshold 0 selects
    every walker, so each step slices rows and queues them."""
    cparams = pot.init_committee(CFG, jax.random.PRNGKey(0))
    eng = acq.FusedEngine(_forces, cparams, 0.0, impl="xla", monitor=mon)
    fleet = WalkerFleet(eng, _x0(4), FleetConfig(noise=0.0, patience=10 ** 6))
    ex = Exchange([], PredictionPool([], None, engine=eng),
                  OracleInputBuffer(),
                  ExchangeConfig(std_threshold=0.0, min_interval=min_interval),
                  monitor=mon, fleet=fleet)
    return ex, eng


def _trainer(mon, steps=3):
    cparams = pot.init_committee(CFG, jax.random.PRNGKey(1))
    tr = CommitteeTrainer(_loss, cparams, steps=steps, batch=2,
                          replay_capacity=16, monitor=mon)
    x = _x0(8, seed=2)
    tr.add_blocks(list(zip(x, np.zeros_like(x))))
    return tr


# ------------------------------------------------------------ timers
def test_span_totals_and_self_time_under_nesting():
    mon = Monitor()
    for _ in range(2):
        with mon.span("outer", step=1):
            time.sleep(0.01)
            with mon.span("inner"):
                time.sleep(0.02)
                with mon.span("leaf"):
                    time.sleep(0.01)
    t = mon.report()["timers"]
    assert t["outer"]["count"] == t["inner"]["count"] == 2
    assert t["outer"]["total_s"] >= t["inner"]["total_s"] \
        >= t["leaf"]["total_s"] >= 0.02
    # self time = own time less the direct children's
    for parent, child in (("outer", "inner"), ("inner", "leaf")):
        assert t[parent]["self_s"] == pytest.approx(
            t[parent]["total_s"] - t[child]["total_s"], abs=1e-9)
    assert t["leaf"]["self_s"] == t["leaf"]["total_s"]
    assert t["outer"]["self_s"] >= 0.02 and t["inner"]["self_s"] >= 0.04
    assert set(t["outer"]) == {"mean_s", "max_s", "count", "total_s",
                               "self_s"}


def test_span_closes_on_exception():
    mon = Monitor()
    with pytest.raises(RuntimeError):
        with mon.span("outer"):
            with mon.span("inner"):
                raise RuntimeError("boom")
    with mon.span("after"):
        pass
    t = mon.report()["timers"]
    assert t["outer"]["count"] == t["inner"]["count"] == 1
    # the stack unwound: a later span is nobody's child
    assert t["after"]["self_s"] == t["after"]["total_s"]
    assert t["outer"]["self_s"] == pytest.approx(
        t["outer"]["total_s"] - t["inner"]["total_s"], abs=1e-9)


def test_span_stacks_are_per_thread():
    """A span open on one thread is not the parent of a span on
    another: each thread's self time counts only its own children."""
    mon = Monitor()
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with mon.span("worker"):
            inside.set()
            release.wait(5)
            time.sleep(0.02)

    th = threading.Thread(target=worker)
    with mon.span("main"):
        th.start()
        assert inside.wait(5)
        with mon.span("main.child"):
            release.set()
            th.join(5)
    assert not th.is_alive()
    t = mon.report()["timers"]
    assert t["worker"]["self_s"] == t["worker"]["total_s"] >= 0.02
    assert t["main"]["self_s"] == pytest.approx(
        t["main"]["total_s"] - t["main.child"]["total_s"], abs=1e-9)


def test_spans_from_many_threads_lose_no_update():
    """More threads than cores open nested spans on one Monitor with a
    short switch interval: every span is counted, and every thread's
    parent self time excludes exactly its own child's time."""
    import os
    import sys

    mon = Monitor()
    n_threads, n_spans = 2 * (os.cpu_count() or 2), 300
    errors = []

    def worker(k):
        try:
            for i in range(n_spans):
                with mon.span("outer", step=i):
                    with mon.span("inner"):
                        pass
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert not errors
    t = mon.report()["timers"]
    assert t["outer"]["count"] == t["inner"]["count"] == n_threads * n_spans
    assert t["outer"]["self_s"] == pytest.approx(
        t["outer"]["total_s"] - t["inner"]["total_s"], rel=1e-9, abs=1e-12)


def test_exchange_predict_totals_the_fleet_step():
    """``exchange.predict`` keeps its meaning: the time of
    ``WalkerFleet.step()``, dispatch and device wait included; the
    engine's spans are its children."""
    mon = Monitor()
    ex, _ = _exchange(mon)
    ex.step()                                    # compiles
    mon0 = {k: v["total_s"] for k, v in mon.report()["timers"].items()}
    step = ex.fleet.step
    took = []

    def timed():
        t0 = time.perf_counter()
        out = step()
        took.append(time.perf_counter() - t0)
        return out

    ex.fleet.step = timed
    for _ in range(3):
        assert ex.step() is None
    t = mon.report()["timers"]
    predict = t["exchange.predict"]["total_s"] - mon0["exchange.predict"]
    assert predict >= sum(took)
    assert predict == pytest.approx(sum(took), abs=3 * 1e-3)
    assert t["exchange.predict"]["count"] == 4
    engine = sum(t[n]["total_s"] for n in (
        "engine.dispatch", "engine.wait", "engine.fetch_selected"))
    assert engine <= t["exchange.predict"]["total_s"]
    assert t["exchange.predict"]["self_s"] == pytest.approx(
        t["exchange.predict"]["total_s"] - engine, abs=1e-9)
    assert t["exchange.round"]["count"] == 4
    assert ex.oracle_buffer.snapshot()           # every walker selected


# ----------------------------------------------------- profiler trace
def _host_event_names(log_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name.split("#", 1)[0] for e in line.events)
    return names


def test_profiler_trace_holds_the_fleet_and_train_spans(tmp_path):
    """One fleet step (with a floor long enough to sleep) and one train
    round, traced: every span they open is on the host plane, by the
    name ``SPAN_NAMES`` gives it."""
    mon = Monitor()
    ex, _ = _exchange(mon, min_interval=0.5)
    tr = _trainer(mon)
    ex.step()
    tr.train(steps=1)                            # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        ex.step()
        tr.train()
    names = _host_event_names(tmp_path)
    assert set(FLEET_SPANS + TRAIN_SPANS) <= set(SPAN_NAMES)
    missing = [n for n in FLEET_SPANS + TRAIN_SPANS if n not in names]
    assert not missing, missing
    t = mon.report()["timers"]
    assert t["trainer.dispatch"]["count"] == 1 + 3
    assert t["trainer.round"]["count"] == 2 and t["trainer.sync"]["count"] == 2


# --------------------------------------------------- device programs
def test_programs_and_scopes_are_named_in_the_hlo():
    """The fused step-and-score program, the score program and the train
    step carry their own names, and the layer boundaries inside them
    their named scopes, in the compiled HLO's module names and op
    metadata."""
    mon = Monitor()
    ex, eng = _exchange(mon)
    ex.step()
    eng.score(_x0(3))
    tr = _trainer(mon)
    tr.train(steps=1)
    fleet = ex.fleet
    (step_fn,) = eng._step_cache.values()
    step_hlo = step_fn.lower(eng.cparams, fleet._carry, np.int32(4),
                             np.int32(0), eng.rule_state).compile().as_text()
    (score_fn,) = eng._cache.values()
    score_hlo = score_fn.lower(eng.cparams, jnp.zeros((8, D)), np.int32(3),
                               np.int32(0), eng.rule_state
                               ).compile().as_text()
    xb, yb, size = tr.replay.arrays()
    train_hlo = tr._fused.lower(tr.cstate, xb, yb, np.int32(size),
                                jax.random.PRNGKey(0)).compile().as_text()

    assert "HloModule jit_engine_step_score" in step_hlo
    assert "HloModule jit_engine_score" in score_hlo
    assert "HloModule jit_committee_train_step" in train_hlo
    for hlo, program, scopes in (
            (step_hlo, "engine_step_score", (
                "advance", "committee_forward", "committee_uq", "selection",
                "react", "pack_selected", "descriptor", "mlp")),
            (score_hlo, "engine_score", (
                "committee_forward", "committee_uq", "selection")),
            (train_hlo, "committee_train_step", (
                "loss", "optimizer", "descriptor", "mlp"))):
        for scope in scopes:
            # under vmap and grad a scope reads e.g. vmap(jvp(descriptor))
            assert re.search(rf'op_name="jit\({program}\)/[^"]*\b{scope}\b',
                             hlo), (program, scope)


def test_pallas_kernel_is_named_committee_uq():
    """The Pallas kernel's ``name=`` reaches the program (here in
    interpret mode; tests/test_tpu_compile.py checks the TPU custom
    call)."""
    from repro.kernels import committee_uq as cuq

    preds = jnp.ones((3, 8, 2), jnp.float32)
    jaxpr = str(jax.make_jaxpr(
        lambda p: cuq.committee_uq(p, 0.1, interpret=True))(preds))
    assert "name=committee_uq" in jaxpr
