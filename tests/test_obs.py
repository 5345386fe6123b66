"""Observability subsystem (repro.obs): lifecycle tracing, Chrome-trace
export, live metrics snapshots, and the structured logger.

The two engine-level invariants under test:

  determinism   under a VirtualClock (single-threaded scheduler) with an
                injected ``service_time_fn``, two replays of the same burst
                produce byte-identical ``TraceRecorder.lines()``
  conservation  every submitted rid terminates in *exactly one* event from
                ``TERMINAL_KINDS`` — on the happy path, with deadline/SLO
                fates mixed in, and under sampled FaultPlan chaos on the
                threaded engine (``CHAOS_SEED`` overrides the plan seed,
                mirroring the nightly chaos job)
"""
import dataclasses
import io
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.config import get_snn
from repro.core import init_snn
from repro.obs import export as obs_export
from repro.obs import log as obs_log
from repro.obs import trace as obs_trace
from repro.obs.trace import TERMINAL_KINDS, TraceRecorder
from repro.runtime.faults import FaultPlan
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.metrics import ServingMetrics


def _tiny_cfg():
    return dataclasses.replace(
        get_snn("snn-mnist"), input_hw=(8, 8), conv_channels=(8, 8),
        timesteps=3, num_spe_clusters=4)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_cfg()
    params = init_snn(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _frames(n, cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.random((*cfg.input_hw, cfg.input_channels))
            .astype(np.float32) for _ in range(n)]


def _traced_replay(cfg, params, *, deadline_every=0):
    """One virtual-clock run of a fixed 12-request burst with a traced
    engine and deterministic injected service times; returns (eng, rids)."""
    spec = api.ServeSpec(backend="batched", num_lanes=2, max_batch=4,
                         buckets=(4,), trace=True, keep_logits=False)
    eng = api.Session(cfg, spec, params=params).engine(
        service_time_fn=lambda lane, wall: 0.01 * (lane + 1))
    frames = _frames(12, cfg, seed=5)
    rng = np.random.default_rng(5)
    arrivals = np.cumsum(rng.exponential(2e-3, 12))
    rids = []
    for i, (f, a) in enumerate(zip(frames, arrivals)):
        dl = 1e-9 if deadline_every and i % deadline_every == 0 else None
        rids.append(eng.submit(f, arrival=float(a), deadline_s=dl))
    eng.run()
    return eng, rids


# -- TraceRecorder units -----------------------------------------------------

def test_recorder_emit_read_filter():
    rec = TraceRecorder(capacity=16)
    rec.emit(obs_trace.KIND_SUBMIT, t=0.5, rid=1, workload=2.0)
    rec.emit(obs_trace.KIND_DISPATCH, t=1.0, lane=0, n=3)
    rec.emit(obs_trace.KIND_COMPLETE, t=1.5, lane=0, rid=1)
    assert len(rec) == 3
    evs = rec.events()
    assert [e.seq for e in evs] == [0, 1, 2]
    assert evs[0].get("workload") == 2.0
    assert evs[0].get("missing", "d") == "d"
    assert evs[0].to_dict() == {"seq": 0, "ts": 0.5, "kind": "submit",
                                "rid": 1, "workload": 2.0}
    assert [e.kind for e in rec.events(obs_trace.KIND_DISPATCH)] \
        == ["dispatch"]
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_recorder_disabled_is_noop():
    rec = TraceRecorder(capacity=16, enabled=False)
    rec.emit(obs_trace.KIND_SUBMIT, t=0.0, rid=1)
    assert len(rec) == 0 and rec.lines() == []


def test_recorder_ring_eviction_counts_dropped():
    rec = TraceRecorder(capacity=2)
    for i in range(5):
        rec.emit(obs_trace.KIND_ROUND, t=float(i))
    assert len(rec) == 2
    assert rec.dropped == 3
    assert [e.ts for e in rec.events()] == [3.0, 4.0]   # oldest evicted


def test_recorder_rejects_bad_capacity():
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_format_event_stable_float_rendering():
    rec = TraceRecorder()
    rec.emit(obs_trace.KIND_BATCH_DONE, t=1.0 / 3.0, lane=1, n=4, svc=0.25)
    line, = rec.lines()
    # fixed 9-digit precision, sorted data keys, no seq in the line
    assert line == "0.333333333 batch_done lane=1 n=4 svc=0.250000000"


def test_terminal_kinds_cover_request_fates():
    assert TERMINAL_KINDS == {"complete", "reject", "deadline", "cancel",
                              "failed"}


# -- determinism + conservation (virtual clock) ------------------------------

def test_virtual_trace_two_replays_byte_identical(tiny):
    cfg, params = tiny
    eng1, _ = _traced_replay(cfg, params)
    eng2, _ = _traced_replay(cfg, params)
    lines1, lines2 = eng1.trace.lines(), eng2.trace.lines()
    assert lines1, "traced run recorded nothing"
    assert lines1 == lines2
    assert eng1.trace.dropped == 0


def test_virtual_trace_conservation(tiny):
    cfg, params = tiny
    eng, rids = _traced_replay(cfg, params)
    term = eng.trace.terminal_rids()
    assert set(term) == set(rids)
    assert all(kinds == ["complete"] for kinds in term.values())
    # the trace agrees with the engine's own resolution accounting
    assert {r.rid for r in eng.completed} == set(rids)


def test_virtual_trace_conservation_with_deadline_fates(tiny):
    cfg, params = tiny
    eng, rids = _traced_replay(cfg, params, deadline_every=3)
    term = eng.trace.terminal_rids()
    assert set(term) == set(rids)
    assert all(len(kinds) == 1 for kinds in term.values())
    fates = {kinds[0] for kinds in term.values()}
    assert "deadline" in fates and "complete" in fates
    expired = {r.rid for r in eng.expired}
    assert expired == {rid for rid, kinds in term.items()
                       if kinds == ["deadline"]}


def test_threaded_chaos_trace_conservation(tiny):
    """Sampled FaultPlan chaos on the threaded engine: whatever mix of
    crashes/transients/storms the seed draws, every rid still gets exactly
    one terminal trace event (CHAOS_SEED replays the nightly job's draw)."""
    cfg, params = tiny
    seed = int(os.environ.get("CHAOS_SEED", "20260809"))
    plan = FaultPlan.sample(seed=seed, num_lanes=2)
    spec = api.ServeSpec(backend="batched", num_lanes=2, max_batch=4,
                         buckets=(4,), threaded=True, keep_logits=False,
                         trace=True, restart_budget=2,
                         restart_backoff_s=0.005, fault_plan=plan)
    eng = api.Session(cfg, spec, params=params).engine()
    rids = [eng.submit(f, arrival=0.0) for f in _frames(16, cfg, seed=3)]
    storm_frame = _frames(1, cfg, seed=4)[0]
    for a in plan.storm_arrivals():
        rids.append(eng.submit(storm_frame, arrival=float(a)))
    eng.warmup()
    eng.run()
    term = eng.trace.terminal_rids()
    assert set(term) == set(rids), f"seed={seed}"
    dupes = {rid: kinds for rid, kinds in term.items() if len(kinds) != 1}
    assert not dupes, f"non-exactly-once fates {dupes} seed={seed}"


# -- Chrome trace export -----------------------------------------------------

def test_chrome_trace_valid_and_loadable(tiny, tmp_path):
    cfg, params = tiny
    eng, rids = _traced_replay(cfg, params)
    doc = obs_export.chrome_trace(eng.trace)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert evs
    for ev in evs:
        assert ev["ph"] in {"M", "X", "i", "s", "t", "f"}
        assert "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0 and ev["ts"] >= 0.0
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"scheduler", "requests"} <= names
    assert any(n.startswith("lane ") for n in names)
    # every request renders as one flow: one start, one finish
    for rid in rids:
        starts = [e for e in evs if e["ph"] == "s" and e["id"] == rid]
        ends = [e for e in evs if e["ph"] == "f" and e["id"] == rid]
        assert len(starts) == 1 and len(ends) == 1, rid
    # round-trips through JSON on disk
    path = str(tmp_path / "trace.json")
    n = obs_export.write_chrome_trace(eng.trace, path)
    with open(path) as f:
        assert len(json.load(f)["traceEvents"]) == n == len(evs)


def test_render_timeline_lines_and_elision(tiny):
    cfg, params = tiny
    eng, _ = _traced_replay(cfg, params)
    text = obs_export.render_timeline(eng.trace)
    assert len(text.splitlines()) == len(eng.trace)
    short = obs_export.render_timeline(eng.trace, limit=3).splitlines()
    assert len(short) == 4 and "elided" in short[0]


# -- live metrics snapshots --------------------------------------------------

class _Gate:
    """Fault hook blocking the first dispatched execution until released —
    pins one lane busy so the mid-burst snapshot is race-free."""

    def __init__(self):
        self.entered = threading.Event()
        self.release = threading.Event()
        self._armed = True
        self._lock = threading.Lock()

    def __call__(self, lane, attempt):
        with self._lock:
            arm, self._armed = self._armed, False
        if arm:
            self.entered.set()
            self.release.wait(timeout=30.0)


def test_live_metrics_snapshot_mid_burst(tiny):
    cfg, params = tiny
    gate = _Gate()
    eng = ServingEngine(params, cfg, EngineConfig(
        num_lanes=2, max_batch=4, buckets=(4,), threaded=True, trace=True,
        fault_hook=gate))
    live = api.LiveServer(eng.serve_forever())
    n = 12
    handles = []
    try:
        handles = [live.submit(f) for f in _frames(n, cfg, seed=7)]
        assert gate.entered.wait(timeout=30.0)
        snap = live.metrics()             # taken WHILE a batch is pinned
        assert snap.live
        assert snap.lanes_total == 2 and snap.lanes_alive == 2
        assert snap.in_flight >= 1
        assert snap.outstanding >= 1
        assert snap.served + snap.outstanding <= n
        assert snap.trace_enabled and snap.trace_events > 0
        d = snap.to_dict()
        assert d["in_flight"] == snap.in_flight
        assert isinstance(d["lane_served"], list)
    finally:
        gate.release.set()
        for h in handles:
            h.result(timeout=60.0)
        live.shutdown(timeout=60.0)
    final = live.metrics()
    assert not final.live
    assert final.served == n and final.outstanding == 0
    # the trace saw the same story: one terminal event per rid
    term = live.trace().terminal_rids()
    assert len(term) == n
    assert all(kinds == ["complete"] for kinds in term.values())


def test_snapshot_on_virtual_engine_after_run(tiny):
    cfg, params = tiny
    eng, rids = _traced_replay(cfg, params)
    snap = eng.snapshot()
    assert snap.served == len(rids) and snap.outstanding == 0
    assert not snap.live
    assert snap.ts > 0.0                   # stamped off the bound clock
    assert snap.trace_events == len(eng.trace)


# -- metrics summary + workload-prediction observability ---------------------

def test_summary_has_wall_and_in_flight(tiny):
    cfg, params = tiny
    eng, _ = _traced_replay(cfg, params)
    s = eng.summary()
    assert s["in_flight"] == 0.0
    assert s["wall_s"] >= 0.0
    assert 0.0 <= s["workload_residual"] <= 1.0
    assert s["residual_rounds"] >= 0.0


def test_skip_fraction_accumulation():
    m = ServingMetrics()
    m.skip.fold((0.5,), (1,))
    m.skip.fold((1.0,), (1,))
    s = m.summary()
    assert s["skip_batches"] == 2.0
    assert s["skip_sparsity"] == pytest.approx(0.75)


def test_skip_table_fraction_bounds():
    from repro.kernels.ops import skip_table_fraction
    zeros = jnp.zeros((2, 1, 8, 8, 4), jnp.float32)
    assert float(skip_table_fraction(zeros, 3)) == 1.0
    # dense input: every row block sees spikes, nothing is skippable
    ones = jnp.ones_like(zeros)
    assert float(skip_table_fraction(ones, 3)) == 0.0
    # one active row in one timestep: some blocks empty, some not
    sparse = zeros.at[0, 0, 0, :, :].set(1.0)
    for aprc in (True, False):
        f = float(skip_table_fraction(sparse, 3, aprc=aprc))
        assert 0.0 < f < 1.0


# -- structured logger -------------------------------------------------------

def test_logger_namespacing_and_levels():
    buf = io.StringIO()
    root = obs_log.configure_logging("info", {"serve": "debug"}, stream=buf)
    try:
        assert root.name == "repro"
        assert obs_log.get_logger("serve").name == "repro.serve"
        assert obs_log.get_logger().name == "repro"
        obs_log.get_logger("serve").debug("dbg %d", 1)
        obs_log.get_logger("train").info("step done")
        obs_log.get_logger("train").debug("hidden")
        out = buf.getvalue()
        assert "dbg 1" in out and "step done" in out
        assert "hidden" not in out
        # idempotent: re-configuring must not stack handlers
        n = len(root.handlers)
        obs_log.configure_logging("warning", stream=io.StringIO())
        assert len(root.handlers) == n
        with pytest.raises(ValueError):
            obs_log.configure_logging("verbose")
    finally:
        # restore the library-quiet default for the rest of the suite
        obs_log.configure_logging("warning", {"serve": "warning"})


def test_library_default_is_quiet():
    # importing repro must not chatter: unconfigured subsystem loggers sit
    # at WARNING via the repro root
    lg = obs_log.get_logger("somewhere")
    assert lg.getEffectiveLevel() >= 30


# -- program spans and the skip-table counter (obs.spans, obs.skip) ----------

from repro.core.snn_model import skip_cells  # noqa: E402
from repro.obs import skip as obs_skip  # noqa: E402
from repro.obs import spans as obs_spans  # noqa: E402


def _seg_cfg():
    # three conv layers, the last a non-firing readout: skip_fractions
    # covers layers 1 (2 row blocks) and 2 (3 row blocks)
    return dataclasses.replace(
        get_snn("snn-seg"), input_hw=(12, 8), conv_channels=(4, 4, 1),
        timesteps=2, num_spe_clusters=4)


@pytest.fixture
def skip_counter():
    """The process counter, empty before and after a test."""
    obs_skip.counter().reset()
    yield obs_skip.counter()
    obs_skip.counter().reset()


def _xplane_spans(log_dir):
    """(name, start, end, line) of every ``snn.*`` event in the trace."""
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(str(log_dir / "plugins/profile/*/*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("snn."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                (plane.name, line.name)))
    return out


def test_program_spans_nest_root_to_phase(tiny, tmp_path):
    cfg, params = tiny
    frames = np.stack(_frames(2, cfg))
    inf = api.Session(cfg, api.ExecutionSpec(backend="batched"),
                      params=params)
    trn = api.Session(cfg, api.TrainSpec(backend="batched"), params=params)
    inf.infer(frames)                         # compile outside the trace
    trn.train_step(frames, np.array([1, 2]))
    jax.profiler.start_trace(str(tmp_path))
    try:
        inf.infer(frames)
        trn.train_step(frames, np.array([3, 4]))
    finally:
        jax.profiler.stop_trace()
    found = _xplane_spans(tmp_path)
    assert {n for n, *_ in found} <= set(obs_spans.SPAN_NAMES)
    roots = [s for s in found if s[0] in obs_spans.ROOTS]
    assert sorted(r[0] for r in roots) == ["snn.infer", "snn.train_step"]
    want = {"snn.infer": ["snn.stage", "snn.dispatch", "snn.sync"],
            "snn.train_step": ["snn.stage", "snn.dispatch",
                               "snn.update_engines", "snn.sync"]}
    for name, lo, hi, line in roots:
        kids = sorted((s for s in found if s[0] in obs_spans.PHASES
                       and s[3] == line and lo <= s[1] and s[2] <= hi),
                      key=lambda s: s[1])
        assert [k[0] for k in kids] == want[name]
    phases = [s for s in found if s[0] in obs_spans.PHASES]
    assert all(any(r[3] == p[3] and r[1] <= p[1] and p[2] <= r[2]
                   for r in roots) for p in phases)


def test_skip_cells_hand_counts():
    # APRC 3x3: layer 1 sees 14 rows -> 16 output rows, 2 blocks of 8;
    # layer 2 sees 16 -> 18 rows, 3 blocks; the hoisted layer 0 has none
    cfg = _seg_cfg()
    assert skip_cells(cfg, 2, 2) == (2 * 2 * 2, 2 * 2 * 3)
    assert skip_cells(cfg, 2, 2, hoist=False) == (8, 8, 12)
    mnist = dataclasses.replace(_tiny_cfg(), conv_channels=(8, 8, 8))
    assert skip_cells(mnist, 4, 3) == (3 * 4 * 2, 3 * 4 * 2)


def test_skip_counter_off_adds_no_transfer(skip_counter):
    cfg = _seg_cfg()
    sess = api.Session(cfg, api.ExecutionSpec(backend="pallas"))
    frames = np.stack(_frames(2, cfg))
    sess.infer(frames)                        # compile
    assert not obs_spans.tracing()            # no profiler: counter off
    with jax.transfer_guard_device_to_host("disallow"):
        sess.infer(frames)
    assert skip_counter.read().calls == 0


def test_skip_counter_folds_the_traced_calls_fractions(skip_counter,
                                                       tmp_path):
    cfg = _seg_cfg()
    sess = api.Session(cfg, api.ExecutionSpec(backend="pallas"))
    frames = np.stack(_frames(2, cfg))
    frames[1, 4:] = 0.0                       # a quiet half: blocks to skip
    sess.infer(frames)                        # compile, not counted
    jax.profiler.start_trace(str(tmp_path))
    try:
        outs = [sess.infer(frames), sess.infer(frames[::-1].copy())]
    finally:
        jax.profiler.stop_trace()
    sess.infer(frames)                        # after the trace: not counted
    reading = skip_counter.read()
    cells = skip_cells(cfg, 2, cfg.timesteps)
    fr = np.array([[float(f) for f in o.skip_fractions] for o in outs])
    assert reading.calls == 2 and reading.cells == tuple(2 * c for c in cells)
    assert reading.skipped == pytest.approx(tuple(fr.sum(0) * cells))
    assert reading.share == pytest.approx(
        (fr * cells).sum() / (2 * sum(cells)))
    assert 0.0 < reading.share < 1.0


def test_skip_counter_rejects_mismatched_layers():
    with pytest.raises(ValueError, match="layers"):
        obs_skip.SkipCounter().fold((0.5,), (4, 4))


def test_engine_skip_metrics_read_the_counter():
    cfg = _seg_cfg()
    spec = api.ServeSpec(backend="pallas", num_lanes=1, max_batch=2,
                         buckets=(2,), keep_logits=False)
    eng = api.Session(cfg, spec).engine()
    for f in _frames(4, cfg, seed=3):
        eng.submit(f, arrival=0.0)
    eng.run()
    snap = eng.snapshot()
    reading = eng.metrics.skip.read()
    assert snap.skip_batches == reading.calls == 2
    assert reading.cells == tuple(2 * c for c in skip_cells(cfg, 2, 2))
    assert snap.skip_sparsity == pytest.approx(reading.share)
    assert eng.summary()["skip_batches"] == 2.0
