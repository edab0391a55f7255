"""Operations and bytes the algorithm needs, from a configuration's shapes.

The step counts depend on the architecture and come from the program
side of the one the configuration names (``archs/<model>.py``, through
``harness.arch``); the UQ kernel's bytes do not.  ``PERF.md`` derives
every term.
"""
from __future__ import annotations

import harness


def fleet_step_flops(cfg, n_walkers: int) -> float:
    """One fused fleet step of all K members on ``n_walkers`` walkers."""
    return harness.arch(cfg).program.fleet_step_flops(cfg, n_walkers)


def train_step_flops(cfg, batch: int) -> float:
    """One fused train step of all K members on ``batch`` structures
    each."""
    return harness.arch(cfg).program.train_step_flops(cfg, batch)


def uq_kernel_bytes(n_members: int, rows: int, d: int) -> float:
    """HBM bytes of one ``committee_uq`` call: the (K, n, d) float32
    predictions read once; the (n, d) mean and the (n, 128) statistics
    slab written once."""
    return 4.0 * (n_members * rows * d + rows * d + rows * 128)
