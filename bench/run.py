#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; everything that belongs to it is
found by name:

  bench/configs/<config>.json     model sizes, as run
  bench/traffic/<traffic>.json    the mix: which driver, its parameters
  bench/drivers/<driver>.py       the general loop that offers that mix
  bench/limits/<cell>.json        the limit of each number compared
  bench/metrics/<metric>.py       one reader per per-layer metric

Set-up (imports, weights made on the device from ``--seed``, compiling and
warming exactly the shapes the cell uses) is timed as ``setup_s``; then the
driver measures for ``--seconds``, checks the outputs of the timed path
against the plain reference (``bench/reference.py``) and prints every number
compared beside its limit.  With ``--trace 1`` a profiler window inside the
measured window gives the per-layer metrics and a breakdown instead of the
end-to-end ones.  The last line of standard output is one JSON object.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
OUT_DIR = BENCH / "out"

if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, bad
    files)."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark by path (names may hold dots and
    dashes)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              str(path.relative_to(BENCH)))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with every file it names."""
    name: str
    entry: Dict
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _reports(metric: Dict, cell: str, e2e_names: List[str]) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads`` key
    lists; without one, every cell (an end-to-end metric) or every cell that
    reports the end-to-end metric it moves (a per-layer metric)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def resolve(name: str, root: Path = ROOT,
            bench: Optional[Dict] = None) -> Cell:
    """The cell named ``name`` and its files, found by name in ``bench``
    (default: the checkout's ``BENCHMARK.json``)."""
    if bench is None:
        bench_file = root / "BENCHMARK.json"
        if not bench_file.is_file():
            raise BenchError(f"no BENCHMARK.json in {root}")
        bench = load_json(bench_file)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise BenchError(f"unknown workload {name!r}; have {sorted(entries)}")
    entry = entries[name]
    bdir = root / "bench"
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(
        name=name, entry=entry,
        config=load_json(bdir / "configs" / f"{entry['config']}.json"),
        traffic=load_json(bdir / "traffic" / f"{entry['traffic']}.json"),
        limits=load_json(bdir / "limits" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


# -- compile accounting -------------------------------------------------------
class CompileStats:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events.  JAX offers no way to remove a listener, so one instance per
    process is registered (``compile_stats()``)."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


_STATS: List[CompileStats] = []


def compile_stats() -> CompileStats:
    if not _STATS:
        _STATS.append(CompileStats())
    return _STATS[0]


# -- profiler window ----------------------------------------------------------
class Profiler:
    """A ``jax.profiler`` window of ``length`` seconds starting ``after``
    seconds into the measured window (``length`` None: until ``stop``); a
    no-op when tracing is off.  The benchmark's host spans (``span``) land
    in the same trace."""

    def __init__(self, enabled: bool, out_dir: Path, after: float,
                 length: Optional[float]):
        self.enabled = enabled
        self.dir = out_dir
        self.after = after
        self.length = length
        self.state = "idle"            # idle -> running -> done

    def poll(self, t_rel: float) -> None:
        """Start or stop the window at ``t_rel`` seconds into the measured
        window."""
        if not self.enabled or self.state == "done":
            return
        import jax
        if self.state == "idle" and t_rel >= self.after:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True)
            # no Python tracer: it would slow every host thread it watches
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.t_start = self._mark("bench_profile_start")
            self.state = "running"
        elif self.state == "running" and self.length is not None \
                and t_rel >= self.after + self.length:
            self.stop()

    def stop(self) -> None:
        if self.state == "running":
            import jax
            self.t_stop = self._mark("bench_profile_stop")
            jax.profiler.stop_trace()
        self.state = "done"

    @staticmethod
    def _mark(name: str) -> float:
        """A zero-length host span; returns its ``perf_counter`` time, which
        ties the host clock to the trace's."""
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            pass
        return t

    def xplane(self) -> Optional[Path]:
        found = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        return found[-1] if found else None


def span(name: str, **kw):
    """A host span of the benchmark's own in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


# -- what a driver gets and gives ---------------------------------------------
@dataclass
class Run:
    """Everything one run of a cell needs; handed to the driver."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    peak: Dict
    stats: CompileStats
    profiler: Profiler
    program_cfg: Any = None

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclass
class Check:
    """One number compared with the plain reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Outcome:
    """A driver's result: counts, end-to-end values, the comparison, and
    what the per-layer readers read."""
    attempted: int
    failed: int
    window_start: float                   # perf_counter at window start
    end_to_end: Dict[str, float]
    checks: List[Check]
    memory_peak_bytes: int
    layer: Dict[str, Any] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)
    compiles_in_window: int = 0


def open_window() -> float:
    """Start the measured window: set-up's objects are collected once and
    frozen, so that the collector does not walk them again inside the
    window.  Returns the window's start on ``perf_counter``."""
    gc.collect()
    gc.freeze()
    return time.perf_counter()


def close_window() -> None:
    gc.unfreeze()


# -- the host around the window -----------------------------------------------
_PRESSURE = ("cpu", "memory", "io")


def host_counters() -> Dict[str, float]:
    """Counters that tell a stalled host from a slow device: CPU seconds the
    hypervisor stole from this machine (``/proc/stat``), seconds in which
    some task waited for CPU, memory or IO (``/proc/pressure``), and this
    process's involuntary context switches.  A counter the kernel does not
    offer is left out."""
    import os
    import resource
    out: Dict[str, float] = {}
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        if cpu[0] == "cpu" and len(cpu) > 8:
            out["steal_s"] = int(cpu[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        pass
    for kind in _PRESSURE:
        try:
            with open(f"/proc/pressure/{kind}") as f:
                some = f.readline().split()
            out[f"{kind}_wait_s"] = int(some[-1].split("=")[1]) / 1e6
        except (OSError, ValueError, IndexError):
            pass
    out["invol_switches"] = float(
        resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw)
    return out


def stall_note(step_ends: List[float], t0: float,
               before: Dict[str, float]) -> str:
    """One stderr line on the window's steadiness: its longest step beside
    the median step, and how far each host counter moved over the window."""
    import numpy as np
    after = host_counters()
    steps = np.diff(np.asarray([t0, *step_ends]))
    if steps.size == 0:
        return "host: no step finished in the window"
    k = int(np.argmax(steps))
    moved = ", ".join(f"{name} {after[name] - before[name]:.3f}"
                      for name in before if name in after)
    return (f"host: longest step {steps[k]:.4f} s ending "
            f"{step_ends[k] - t0:.2f} s into the window (median "
            f"{float(np.median(steps)):.4f} s); over the window {moved}")


def program_config(cfg: Dict):
    """The program's ``SNNConfig`` holding exactly the sizes of the
    benchmark's configuration file."""
    import dataclasses
    from repro.config import get_snn
    return dataclasses.replace(
        get_snn(cfg["model"]),
        input_hw=tuple(cfg["input_hw"]),
        input_channels=int(cfg["input_channels"]),
        conv_channels=tuple(cfg["conv_channels"]),
        kernel_size=int(cfg["kernel_size"]),
        dense_units=tuple(cfg["dense_units"]),
        timesteps=int(cfg["timesteps"]),
        v_threshold=float(cfg["v_threshold"]),
        aprc=bool(cfg["aprc"]),
        num_spe_clusters=int(cfg["num_spe_clusters"]),
        num_spes_per_cluster=int(cfg["num_spes_per_cluster"]))


def make_weights(seed: int, cfg: Dict):
    """The run's weights, made on the device in one jitted call from the
    seed."""
    import functools
    import jax
    import reference
    return jax.jit(functools.partial(reference.weights, cfg=cfg))(
        prng_key(seed))


def prng_key(seed: int):
    """A JAX key from a seed of any size (the driver's exceed 32 bits)."""
    import jax
    import numpy as np
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def rng(seed: int, *stream: int):
    """The numpy generator of one stream of the run's inputs."""
    import numpy as np
    return np.random.default_rng([seed, *stream])


# -- the run ------------------------------------------------------------------
def _device_info(run: Run, outcome: Outcome) -> Dict:
    import jax
    d = run.devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": int(outcome.memory_peak_bytes)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``, as JAX reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _per_layer(run: Run, outcome: Outcome, reduced) -> Dict[str, Dict]:
    """Each per-layer metric of the cell that its reader finds."""
    ctx = {"cell": run.cell, "config": run.cell.config,
           "traffic": run.cell.traffic, "peak": run.peak,
           "chips": run.cell.chips, "trace": reduced, **outcome.layer}
    out = {}
    for m in run.cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(run: Run) -> Dict:
    """Drive the cell and assemble the result line."""
    driver = load_module(BENCH / "drivers"
                         / f"{run.cell.traffic['driver']}.py")
    outcome = driver.drive(run)
    result: Dict[str, Any] = {
        "correct": all(c.ok for c in outcome.checks) and bool(outcome.checks),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
    }
    setup_s = outcome.window_start - T_START
    device = _device_info(run, outcome)
    for note in outcome.notes:
        run.log(note)
    run.log(f"setup_s {setup_s!r}, of which backend compiles "
            f"{run.stats.compile_s!r} s; persistent-cache hits "
            f"{run.stats.cache_hits}; compiles in the window "
            f"{outcome.compiles_in_window}")
    if run.trace:
        import trace_reduce
        path = run.profiler.xplane()
        if path is None:
            raise BenchError("traced run left no profiler trace")
        try:
            reduced = trace_reduce.reduce(path, chips=run.cell.chips)
        except ValueError as e:
            raise BenchError(f"trace {path}: {e}") from e
        metrics = _per_layer(run, outcome, reduced)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = reduced.breakdown()
    else:
        metrics = {}
        for m in run.cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" \
                else outcome.end_to_end.get(m["name"])
            if value is None:
                raise BenchError(f"driver gave no {m['name']}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax() -> None:
    """The persistent compile cache lives at a fixed path inside the
    checkout, and keeps every program.  It does not follow
    ``JAX_COMPILATION_CACHE_DIR``: a directory from the environment may be
    shared by two checkouts under comparison, and then one side's compiles
    would show as the other's cache hits.  Nor does it follow the
    environment's size limit: with one set, JAX writes an access-time file
    beside each entry and fails every later write, so that every run
    compiles, once the directory holds an entry written without one."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def prepare(args, *, require_tpu: bool = True,
            cell: Optional[Cell] = None,
            peak: Optional[Dict] = None) -> Run:
    """Resolve the cell, find the chips and the peak table row, and set up
    compile accounting and the profiler."""
    cell = cell if cell is not None else resolve(args.workload)
    configure_jax()
    stats = compile_stats()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {platform!r} "
                         f"({len(devices)} device(s))")
    if len(devices) < cell.chips:
        raise BenchError(f"the cell asks for {cell.chips} chips; JAX found "
                         f"{len(devices)}")
    if require_tpu:
        from repro.kernels import ops
        if ops.default_interpret():
            raise BenchError("the Pallas kernels would run interpreted")
    if peak is None:
        peaks = load_json(BENCH / "peaks.json")
        kind = devices[0].device_kind
        if kind not in peaks:
            raise BenchError(f"device kind {kind!r} is not in "
                             f"bench/peaks.json ({sorted(peaks)})")
        peak = peaks[kind]
    length = cell.traffic.get("trace_seconds")
    profiler = Profiler(bool(args.trace), OUT_DIR / cell.name / "trace",
                        float(cell.traffic.get("trace_after_s", 1.0)),
                        None if length is None else float(length))
    return Run(cell=cell, seed=args.seed, seconds=args.seconds,
               trace=bool(args.trace), devices=devices[:cell.chips],
               peak=peak, stats=stats, profiler=profiler,
               program_cfg=program_config(cell.config))


def main(argv=None, *, require_tpu: bool = True,
         cell: Optional[Cell] = None, peak: Optional[Dict] = None) -> int:
    args = parse_args(argv)
    try:
        run = prepare(args, require_tpu=require_tpu, cell=cell, peak=peak)
        result = execute(run)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    gc.collect()
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:                      # noqa: BLE001 — report, then fail
        traceback.print_exc()
        code = 1
    sys.exit(code)
