"""Benchmark entry point: one section per paper table/claim.

  speedup      — SI S2 analytic speedup model, 3 use cases (Eqs. 1-13)
  overhead     — §3.1 exchange-loop overhead vs committee inference
  scaling      — §2 oracle/generator pool scaling
  committee_uq — fused single-dispatch exchange path vs sequential members
  budget       — cross-round oracle-rate controller: budget tracking under
                 std drift + hot-path overhead vs the default rule
  serving      — queue-batched + mesh-sharded committee serving vs
                 per-call CommitteeServer.predict at request size 1
  train        — fused one-dispatch K-member retraining vs sequential
                 per-member training + weight-refresh host bytes
  memory       — big-committee memory diet: stacked TrainState bytes +
                 step time across K x MemoryPolicy (fp32/bf16/int8)
  fault        — labeled-throughput retention + recovery time under the
                 standard chaos FaultPlan (supervised runtime)
  fleet        — device-resident exploration fleet (one fused
                 advance+score+select dispatch) vs N host generators
  mesh         — production-mesh scale-out: fused score on a real 8-device
                 emulated mesh vs the sequential legacy path, weak-scaling
                 curves, and bit-identity parity flags (only as its own
                 invocation, ``--only mesh``: the device count must be set
                 before jax initializes, so a full run skips it)
  kernels      — Pallas-path microbenchmarks (XLA schedule, host timing)

``python -m benchmarks.run`` runs everything; ``--only <name>`` filters.
The roofline/dry-run tables (launch/roofline.py) are separate because they
need the 512-device XLA_FLAGS subprocess.

``bench_meta()`` is the shared provenance stamp: every BENCH_*.json
writer records the resolved platform / device kind / device count /
process info under a ``"meta"`` key, so a report is interpretable after
the machine that produced it is gone.
"""
from __future__ import annotations

import argparse
import sys
import time


def bench_meta(**extra):
    """Provenance block for BENCH_*.json reports (platform, device kind,
    device/process counts, emulated-device request) plus any benchmark-
    specific extras such as ``mesh_shape``.  Initializes the jax backend —
    writers call it at report time, never at module import."""
    from repro.launch import platform as _platform

    meta = _platform.describe()
    meta["mesh_shape"] = str(extra.pop("mesh_shape", ""))
    meta.update(extra)
    return meta


def _section(title: str):
    print(f"\n{'=' * 70}\n# {title}\n{'=' * 70}", flush=True)


def bench_speedup(simulate: bool):
    from benchmarks import speedup_usecases
    _section("SI S2 speedup model (3 use cases)")
    sys.argv = ["x"] + (["--simulate"] if simulate else [])
    speedup_usecases.main()


def bench_overhead():
    from benchmarks import overhead
    _section("Exchange-loop overhead vs committee inference (paper §3.1)")
    overhead.main()


def bench_scaling():
    from benchmarks import scaling
    _section("Oracle / generator pool scaling (paper §2)")
    scaling.main()


def bench_committee_uq(smoke: bool):
    from benchmarks import committee_uq
    _section("Fused committee-UQ exchange hot path (single dispatch)")
    committee_uq.main(["--smoke"] if smoke else [])


def bench_budget(smoke: bool):
    from benchmarks import budget_controller
    _section("Cross-round budgeted acquisition (oracle-rate controller)")
    budget_controller.main(["--smoke"] if smoke else [])


def bench_serving(smoke: bool):
    from benchmarks import serving_queue
    _section("Queue-batched, mesh-sharded committee serving")
    serving_queue.main(["--smoke"] if smoke else [])


def bench_train(smoke: bool):
    from benchmarks import committee_train
    _section("Fused one-dispatch K-member retraining")
    committee_train.main(["--smoke"] if smoke else [])


def bench_memory(smoke: bool):
    from benchmarks import committee_memory
    _section("Big-committee memory diet (K x MemoryPolicy)")
    committee_memory.main(["--smoke"] if smoke else [])


def bench_fault(smoke: bool):
    from benchmarks import fault_recovery
    _section("Fault recovery: throughput retention under the standard plan")
    fault_recovery.main(["--smoke"] if smoke else [])


def bench_fleet(smoke: bool):
    from benchmarks import exploration_fleet
    _section("Device-resident exploration fleet vs N host generators")
    exploration_fleet.main(["--smoke"] if smoke else [])


def bench_mesh(smoke: bool):
    _section("Production-mesh scale-out (8 emulated devices)")
    # the emulated-device count locks when jax first initializes, and a
    # child process would meet a parent that may already hold the device —
    # so the mesh benchmark runs only as its own top-level invocation:
    # in this process when nothing has touched jax yet (`--only mesh`),
    # and otherwise not at all
    from repro.launch import platform as _platform

    if _platform.backend_initialized():
        print("skipped: jax is already initialized in this process; run "
              "`python -m benchmarks.run --only mesh` (or "
              "benchmarks/mesh_scaleout.py) as its own invocation")
        return
    from benchmarks import mesh_scaleout   # requests 8 devices on import
    mesh_scaleout.main(["--smoke"] if smoke else [])


def bench_kernels():
    _section("Kernel microbenchmarks (XLA schedule on host)")
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = jax.random.PRNGKey(0)

    def timeit(fn, *args, iters=5):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
            (out[0] if isinstance(out, tuple) else out).block_until_ready()
        return (time.perf_counter() - t0) / iters

    print("name,ms_per_call,notes")
    # f32 on host: CPU has no native bf16 — these timings are schedule
    # sanity only; real numbers come from the roofline (TPU target).
    B, T, H, KV, D = 1, 2048, 16, 4, 128
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KV, D), jnp.float32)
    att = jax.jit(lambda q, k, v: ops.attention(q, k, v, causal=True))
    print(f"attention_2k_gqa,{timeit(att, q, k, v) * 1e3:.2f},"
          f"B{B} T{T} H{H}/{KV} D{D}")

    Hn, N = 8, 64
    r = jax.random.normal(ks[0], (B, T, Hn, N))
    w = jax.random.uniform(ks[1], (B, T, Hn, N), minval=0.5, maxval=0.99)
    u = jax.random.normal(ks[2], (Hn, N))
    wkv = jax.jit(lambda r, w: ops.wkv6(r, r, r, w, u))
    print(f"wkv6_2k,{timeit(wkv, r, w) * 1e3:.2f},chunked linear attention")

    P, Ns = 64, 16
    x = jax.random.normal(ks[0], (B, T, Hn, P))
    a = jax.random.uniform(ks[1], (B, T, Hn), minval=0.5, maxval=0.999)
    Bm = jax.random.normal(ks[2], (B, T, Hn, Ns))
    ssd = jax.jit(lambda x, a, Bm: ops.ssd(x, a, Bm, Bm))
    print(f"ssd_2k,{timeit(ssd, x, a, Bm) * 1e3:.2f},chunked SSD scan")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["speedup", "overhead", "scaling", "kernels",
                             "committee_uq", "budget", "serving", "train",
                             "memory", "fault", "fleet", "mesh"])
    ap.add_argument("--simulate", action="store_true",
                    help="run the measured PAL-runtime speedup simulation")
    ap.add_argument("--smoke", action="store_true",
                    help="few iterations (CI)")
    args = ap.parse_args()

    from repro.launch.platform import enable_compile_cache

    enable_compile_cache()     # imports jax, initializes no backend
    t0 = time.time()
    if args.only in (None, "speedup"):
        bench_speedup(args.simulate)
    if args.only in (None, "overhead"):
        bench_overhead()
    if args.only in (None, "scaling"):
        bench_scaling()
    if args.only in (None, "committee_uq"):
        bench_committee_uq(args.smoke)
    if args.only in (None, "budget"):
        bench_budget(args.smoke)
    if args.only in (None, "serving"):
        bench_serving(args.smoke)
    if args.only in (None, "train"):
        bench_train(args.smoke)
    if args.only in (None, "memory"):
        bench_memory(args.smoke)
    if args.only in (None, "fault"):
        bench_fault(args.smoke)
    if args.only in (None, "fleet"):
        bench_fleet(args.smoke)
    if args.only in (None, "mesh"):
        bench_mesh(args.smoke)
    if args.only in (None, "kernels"):
        bench_kernels()
    print(f"\n# total benchmark wall time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
