"""Runs one cell of ``BENCHMARK.json`` once and builds its result line.

Everything that belongs to one cell is found by name: the configuration
file the cell's ``config`` entry names (its ``model`` names the
architecture: ``archs/<model>.py``, the program side, and
``archs/<model>_ref.py``, its plain reference), ``traffic/<traffic>.json``
(its ``loop`` names the module under ``loops/`` that drives it),
``limits/<cell>.json`` (one limit per number compared) and one reader
``metrics/<metric>.py`` per metric the cell reports.  A reader maps the
run's record to a number, or to ``None`` when it finds nothing to read,
and the metric is then left out of the line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import zlib
from typing import Any, Dict, NamedTuple, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
ARCHS = os.path.join(HERE, "archs")
SPAN_NAMES = ("window", "exchange.step", "trainer.train")


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class WindowCompiled(RuntimeError):
    """A program was compiled, or loaded from the compile cache, inside
    the measured window: the run measured set-up work and stands for
    nothing."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Arch(NamedTuple):
    """The two modules of one architecture."""
    program: Any        # make_weights, member_functions, *_step_flops
    reference: Any      # forces, importing nothing of the program


def arch(cfg: Dict[str, Any]) -> Arch:
    """The architecture a configuration names under ``model``: the files
    ``<model>.py`` and ``<model>_ref.py`` of ``ARCHS``, each loaded once.
    A configuration without a ``model``, or one that names none of them,
    is an error that lists the known names."""
    known = sorted(f[:-3] for f in os.listdir(ARCHS)
                   if f.endswith(".py") and not f.endswith("_ref.py"))
    name = cfg.get("model")
    if name not in known:
        raise KeyError(f"configuration {cfg.get('name')!r} names model "
                       f"{name!r}; known: {known}")
    return _load_arch(ARCHS, name)


@functools.lru_cache(maxsize=None)
def _load_arch(folder: str, name: str) -> Arch:
    return Arch(load_module(os.path.join(folder, name + ".py"),
                            "arch_" + name),
                load_module(os.path.join(folder, name + "_ref.py"),
                            "arch_" + name + "_ref"))


def derive_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for one use (``tag``) of the run's ``--seed``: any
    whole number gives distinct, reproducible streams."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 64, zlib.crc32(
        tag.encode())])
    return int(ss.generate_state(1)[0]) & 0x7FFFFFFF


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_spec(workload: str, root: str = ROOT) -> Dict[str, Any]:
    """Everything one cell needs, read from ``BENCHMARK.json`` and the
    files it names."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "cfg": load_json(os.path.join(root, conf["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic",
                                          cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(HERE, "limits",
                                         workload + ".json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"]
                      if applies(m, workload)],
    }


def peaks_for(kind: str) -> Dict[str, float]:
    """The peak table's row for a ``device_kind``; an unknown device is an
    error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]


class Spans:
    """The benchmark's own host spans: seconds per name, and, while a
    trace is taken, the same spans on the profiler's clock."""

    def __init__(self):
        self.total: Dict[str, float] = {}
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.total[name] = self.total.get(name, 0.0) \
                + time.perf_counter() - t0


@dataclasses.dataclass
class Context:
    cell: Dict[str, Any]
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    chips: int
    impl: str
    spans: Spans

    def seed_for(self, tag: str) -> int:
        return derive_seed(self.seed, tag)


def configure_jax(root: str = ROOT):
    """JAX's persistent compilation cache in the fixed ``.jax_cache/`` of
    the checkout, every program cached.  The environment's
    ``JAX_COMPILATION_CACHE_DIR`` is pointed there too, so that code which
    reads it takes the same directory and no cache is shared with another
    checkout."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX finds "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_stats():
    """The runtime's memory statistics of every local device."""
    import jax

    return [d.memory_stats() or {} for d in jax.local_devices()]


def memory_peak_bytes(stats) -> int:
    """The fullest chip's peak: the buffers held (``peak_bytes_in_use``)
    plus what the runtime reserved for the compiled programs' temporaries
    (``peak_bytes_reserved``), which the buffers leave out."""
    return max((int(s.get("peak_bytes_in_use", 0))
                + int(s.get("peak_bytes_reserved", 0)) for s in stats),
               default=0)


class CompileCounter:
    """Programs compiled, or loaded from the compile cache, while active
    (``jax.monitoring``'s backend-compile event spans both); one listener
    per process."""

    _one = None

    def __init__(self):
        import jax

        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    @classmethod
    def get(cls):
        if cls._one is None:
            cls._one = cls()
        cls._one.n = 0
        return cls._one

    def _event(self, event, duration, **kw):
        if self.on and "backend_compile" in event:
            self.n += 1


def run_spec(spec, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, impl: str = "pallas",
             require_tpu: bool = True, root: str = ROOT,
             keep_state: bool = False, compile_cache: bool = True):
    """One run of one cell.  Returns (result line, readings, the
    loop's state when ``keep_state``, context).  ``require_tpu=False``
    and ``compile_cache=False`` are for CPU tests."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cell, traffic = spec["cell"], spec["traffic"]
    device = device_info(cell["chips"], require_tpu)
    peaks = peaks_for(device["kind"]) if require_tpu else None
    if compile_cache:
        configure_jax(root)
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    spans = Spans()
    ctx = Context(cell=cell, cfg=spec["cfg"], traffic=traffic, seed=seed,
                  chips=cell["chips"], impl=impl, spans=spans)
    loop = load_module(os.path.join(HERE, "loops", traffic["loop"] + ".py"),
                       "loop_" + traffic["loop"].replace("-", "_"))
    with spans("setup"):
        st = loop.setup(ctx)
    setup_s = time.perf_counter() - t_start

    counter = CompileCounter.get()
    summary = None
    trace_dir = os.path.join(root, ".bench_trace", f"{cell['name']}-{seed}")
    counter.on = True
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans.annotate = True
        with jax.profiler.trace(trace_dir):
            with spans("window"):
                win = loop.window(ctx, st, seconds)
        spans.annotate = False
    else:
        with spans("window"):
            win = loop.window(ctx, st, seconds)
    counter.on = False
    if counter.n:
        raise WindowCompiled(f"{counter.n} programs compiled or loaded "
                             "inside the measured window")
    mem = memory_stats()
    device["memory_peak_bytes"] = memory_peak_bytes(mem)
    if trace:
        import xplane

        summary = xplane.reduce(*xplane.load(xplane.find(trace_dir),
                                             SPAN_NAMES))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]

    loop.release(st)
    gc.collect()
    t_check = time.perf_counter()
    readings = loop.readings(ctx, st, limits=spec["limits"])
    check_s = time.perf_counter() - t_check
    checks = {k: {"value": float(readings[k]), "limit": spec["limits"][k]}
              for k in spec["limits"]}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())

    record = {"window": win, "setup_s": setup_s, "seconds": seconds,
              "trace": summary, "spans": dict(spans.total),
              "cfg": spec["cfg"], "traffic": traffic, "chips": cell["chips"],
              "peaks": peaks}
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec[section]:
        reader = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                             "metric_" + m["name"].replace(".", "_"))
        value = reader.read(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(win["attempted"]),
              "failed": int(win["failed"]), "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["diagnostics"] = {
        "compiles_in_window": counter.n, "check_s": check_s,
        "memory_stats": mem,
        "window": {k: v for k, v in win.items()
                   if isinstance(v, (int, float))}}
    result["checks"] = checks
    return result, readings, (st if keep_state else None), ctx
