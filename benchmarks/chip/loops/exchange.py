"""Traffic kind ``exchange``: the exploration fleet driven through
``Exchange.step()``.

Set-up builds ``PAL(...)`` with a device-resident ``WalkerFleet`` of
``walkers`` (times the chips, with ``per_chip``) perturbed-lattice
walkers, steps it ``warmup_steps`` times (the first step compiles the
fused step program) and runs once more every host-side slice of the
selected rows the window can ask for.  The window calls
``pal.exchange.step()`` until ``--seconds`` have passed; every step is
whole and ends in the program's own device sync.

Two window steps drawn from the seed are checked: the fleet carry and
the selection-rule state are read before and after each, and the step's
committee statistics right after it.  Once the window has closed the
reference recomputes each from the state it started from.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

import harness
import lattice
import program
import reference as ref


@dataclasses.dataclass
class State:
    pal: Any
    cparams: Any
    n_walkers: int
    capture: tuple
    snaps: Dict[int, Any] = dataclasses.field(default_factory=dict)
    outs: Dict[int, Any] = dataclasses.field(default_factory=dict)


def n_walkers(ctx) -> int:
    t = ctx.traffic
    return t["walkers"] * (ctx.chips if t.get("per_chip") else 1)


def setup(ctx) -> State:
    cfg, t = ctx.cfg, ctx.traffic
    n = n_walkers(ctx)
    with ctx.spans("setup.weights"):
        cparams = harness.arch(cfg).program.make_weights(
            cfg, ctx.seed_for("weights"))
        x0 = lattice.geometries(
            np.random.RandomState(ctx.seed_for("walkers")), n,
            program.base_geometry(cfg), cfg["geometry"]["perturb"])
    with ctx.spans("setup.build"):
        pal = program.build_pal(cfg, t, ctx.seed_for("program"), cparams,
                                impl=ctx.impl, fleet_init=x0)
    with ctx.spans("setup.warmup"):
        for _ in range(t["warmup_steps"]):
            pal.exchange.step()
        # the program slices the selected rows off the device with the
        # round's own count as a static size: one small program per count
        # and layout.  Run the counts the window can reach now, on the
        # arrays it slices, so that none compiles there.
        kmax = min(pal.fleet.nb, math.ceil(
            t["slice_warm_factor"] * t["oracle_budget"] * n) + 16)
        for arr in _sliced_arrays(pal.exchange.step):
            for k in range(1, kmax + 1):
                np.asarray(arr[:k])
    rng = np.random.RandomState(ctx.seed_for("capture"))
    capture = (int(rng.randint(0, 10)), int(rng.randint(10, 40)))
    return State(pal=pal, cparams=cparams, n_walkers=n, capture=capture)


def _sliced_arrays(step, tries: int = 20):
    """The device arrays that ``step`` slices, found by recording
    ``jax.Array.__getitem__`` over steps until one slices (a step that
    selects nothing slices none).  A slice's program is cached by the
    array's shape, type, layout and placement, which on a mesh are the
    compiler's choice, so the arrays themselves are kept and sliced."""
    cls = type(jnp.zeros(()))
    orig = cls.__getitem__
    seen = {}

    def record(self, idx):
        if isinstance(idx, slice):
            seen.setdefault((self.shape, self.dtype, self.sharding,
                             self.committed), self)
        return orig(self, idx)

    cls.__getitem__ = record
    try:
        for _ in range(tries):
            step()
            if seen:
                break
    finally:
        cls.__getitem__ = orig
    if not seen:
        raise RuntimeError(f"no step in {tries} sliced the selected rows; "
                           "their slices would compile inside the window")
    return list(seen.values())


def _snapshot(st: State):
    return (st.pal.fleet.state_dict(), st.pal.engine.state_dict())


def window(ctx, st: State, seconds: float) -> Dict[str, Any]:
    """Whole exchange steps for ``seconds``; returns the work, the time
    and the program's counters over the window."""
    pal = st.pal
    mon = pal.monitor
    fleet = pal.fleet
    nan0 = fleet.stats()["nan_resets"]
    p0 = mon.count("exchange.proposals")
    pred0 = mon.timer("exchange.predict").total
    last = max(st.capture) + 1
    steps = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        if steps in st.capture:
            st.snaps[steps] = _snapshot(st)
        if steps - 1 in st.capture:
            st.snaps[steps - 1] = st.snaps[steps - 1] + _snapshot(st)
        with ctx.spans("exchange.step"):
            pal.exchange.step()
        if steps in st.capture:
            out = fleet.last
            st.outs[steps] = {k: np.asarray(getattr(out, k)) for k in (
                "mean", "scalar_std", "component_std", "mask")}
            st.outs[steps]["n_selected"] = out.n_selected
            st.outs[steps]["selected"] = np.asarray(out.selected)
        steps += 1
        if steps > last and time.perf_counter() >= deadline:
            break
    elapsed = time.perf_counter() - t0
    work = mon.count("exchange.proposals") - p0
    return {
        "work": work, "elapsed": elapsed, "steps": steps,
        "attempted": work,
        "failed": fleet.stats()["nan_resets"] - nan0,
        "predict_s": mon.timer("exchange.predict").total - pred0,
        "step_flops_walkers": st.n_walkers,
        "uq_rows": fleet.nb, "uq_members": ctx.cfg["committee_size"],
        "uq_dim": 3 * ctx.cfg["n_atoms"],
    }


def release(st: State):
    """Drop the program's state before the reference runs; what the
    check needs was read to the host already."""
    st.pal = None


def _outputs(ctx, st: State, i: int, dtype):
    """What a step from snapshot ``i`` produces under the reference in
    ``dtype``: proposals, committee statistics at the program's own
    proposals, and the selection against the threshold it started
    from."""
    t = ctx.traffic
    carry_in, _, carry_out, _ = st.snaps[i]
    n = st.n_walkers
    x = ref.advance(carry_in, t["dt"], t["clip"], t["noise"], dtype)[:n]
    preds = ref.committee_forces(st.cparams, carry_out["x"][:n], ctx.cfg,
                                 dtype)
    mean, sstd, cstd = ref.committee_stats(preds, dtype)
    return {"x": x, "mean": mean, "scalar_std": sstd,
            "component_std": cstd}


def readings(ctx, st: State, dtype=ref.F32, limits=None) -> Dict[str, float]:
    """The numbers compared, worst over the checked steps.  With
    ``dtype=bfloat16`` the reference in bfloat16 takes the program's
    place (the control)."""
    t = ctx.traffic
    n = st.n_walkers
    std_limit = (limits or {}).get("std_gap", 0.0)
    worst: Dict[str, float] = {}
    for i in sorted(st.outs):
        carry_in, rule_in, carry_out, rule_out = st.snaps[i]
        want = _outputs(ctx, st, i, ref.F32)
        thr = float(rule_in[0]["threshold"])
        if _is_f32(dtype):
            got = {"x": carry_out["x"][:n],
                   "mean": st.outs[i]["mean"][:n],
                   "scalar_std": st.outs[i]["scalar_std"][:n],
                   "component_std": st.outs[i]["component_std"][:n]}
            mask = st.outs[i]["mask"][:n].astype(bool)
            n_sel = int(st.outs[i]["n_selected"])
            counts, restarts, flag = ref.patience_update(
                carry_in["counts"][:n], carry_in["restarts"][:n],
                carry_in["flag"][:n], carry_in["x"][:n], mask,
                t["patience"])
            # the oracle candidates: the selected proposals, in walker order
            want_sel = carry_out["x"][:n][mask]
            got_sel = st.outs[i]["selected"]
            selected = abs(len(got_sel) - len(want_sel)) if \
                len(got_sel) != len(want_sel) else int(np.sum(np.any(
                    got_sel != want_sel, axis=-1)))
            react = int(np.sum(counts != carry_out["counts"][:n])
                        + np.sum(restarts != carry_out["restarts"][:n])
                        + np.sum(flag != carry_out["flag"][:n])
                        + np.sum(np.any(carry_out["f"][:n]
                                        != st.outs[i]["mean"][:n], axis=-1))
                        + int(int(carry_out["step"])
                              != int(carry_in["step"]) + 1))
            rule_got = rule_out[0]
        else:
            got = _outputs(ctx, st, i, dtype)
            mask = got["scalar_std"] > thr
            n_sel = int(np.sum(mask))
            rule_got = ref.budget_update(rule_in[0], n_sel, n,
                                         t["oracle_budget"],
                                         t["std_threshold"], dtype=dtype)
            react, selected = 0, 0
        # the controller's step from the state it started from, given the
        # selection the step made
        rule = ref.budget_update(rule_in[0], n_sel, n, t["oracle_budget"],
                                 t["std_threshold"])
        budget = max(
            abs(float(rule_got["threshold"]) - rule["threshold"])
            / rule["threshold"],
            abs(float(rule_got["integral"]) - rule["integral"])
            / max(1.0, abs(rule["integral"])),
            abs(float(rule_got["ema_rate"]) - rule["ema_rate"]),
            float(int(rule_got["rounds"]) != rule["rounds"]))
        scale = float(np.max(want["scalar_std"]))
        near = np.abs(want["scalar_std"] - thr) <= std_limit * scale
        flips = mask != (want["scalar_std"] > thr)
        r = {
            "advance_gap": _gap(got["x"], want["x"]),
            "force_gap": _gap(got["mean"], want["mean"]),
            "std_gap": max(
                float(np.max(np.abs(got["scalar_std"]
                                    - want["scalar_std"]))),
                float(np.max(np.abs(got["component_std"]
                                    - want["component_std"])))) / scale,
            "budget_gap": float(budget),
            "mask_flips_off_threshold": float(np.sum(flips & ~near)),
            "react_mismatch": float(react),
            "selected_mismatch": float(selected),
        }
        for k, v in r.items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


def _is_f32(dtype) -> bool:
    return np.dtype(dtype) == np.dtype(np.float32)


def _gap(got, want) -> float:
    if not np.all(np.isfinite(got)):
        return float("inf")
    return lattice.close(got, want, 0.0)[0]
