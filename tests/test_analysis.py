"""The static checker checks itself: every rule family trips on a known-bad
fixture snippet, suppressions silence exactly what they claim, and the real
tree is clean (the repo-wide run is the regression guard the CI lint gate
enforces).

Fixture snippets are written under tmp_path with the directory layout each
rule scopes on (clock-discipline only fires under serving/runtime/obs
directories; print-ban only inside a ``repro`` package directory).
"""
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import rule_registry, run_analysis
from repro.analysis.base import SourceFile, analyze_file

REPO = Path(__file__).resolve().parent.parent


def _check(tmp_path, relpath, source, rules=None):
    """Write ``source`` at ``relpath`` under tmp_path and analyze it."""
    f = tmp_path / relpath
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(source)
    return run_analysis([f], rules)


def _rules_hit(findings):
    return {f.rule for f in findings}


# -- clock-discipline --------------------------------------------------------

CLOCK_BAD = """\
import time
from datetime import datetime

def poll():
    time.sleep(0.1)
    t = time.perf_counter()
    stamp = datetime.now()
    return t, stamp
"""


def test_clock_rule_trips_in_scope(tmp_path):
    fs = _check(tmp_path, "serving/poller.py", CLOCK_BAD)
    assert _rules_hit(fs) == {"clock-discipline"}
    assert len(fs) == 3                       # sleep, perf_counter, now
    assert all("Clock" in f.message for f in fs)


@pytest.mark.parametrize("scope_dir", ["runtime", "obs"])
def test_clock_rule_covers_all_scope_dirs(tmp_path, scope_dir):
    fs = _check(tmp_path, f"{scope_dir}/mod.py", CLOCK_BAD)
    assert "clock-discipline" in _rules_hit(fs)


def test_clock_rule_ignores_out_of_scope(tmp_path):
    # launch/ CLIs and top-level modules may use wall time freely
    assert _check(tmp_path, "launch/cli.py", CLOCK_BAD) == []
    assert _check(tmp_path, "standalone.py", CLOCK_BAD) == []


def test_clock_rule_catches_from_import_and_alias(tmp_path):
    src = """\
from time import sleep
import time as walltime

def f():
    sleep(1.0)
    return walltime.monotonic()
"""
    fs = _check(tmp_path, "obs/mod.py", src)
    # import line + call site + aliased attribute
    assert len(fs) == 3
    assert _rules_hit(fs) == {"clock-discipline"}


def test_clock_rule_wallclock_site_is_exempt(tmp_path):
    src = """\
import time

class WallClock:
    def now(self):
        return time.perf_counter()

class Other:
    def now(self):
        return time.perf_counter()
"""
    fs = _check(tmp_path, "serving/clock.py", src)
    assert len(fs) == 1                       # only Other.now flagged
    assert fs[0].line == 9


def test_clock_rule_suppression(tmp_path):
    src = """\
import time

def f():
    time.sleep(0.1)  # lint: allow(clock-discipline)
    # lint: allow(clock-discipline)
    time.sleep(0.2)
    time.sleep(0.3)
"""
    fs = _check(tmp_path, "serving/mod.py", src)
    assert len(fs) == 1                       # only the unannotated sleep
    assert fs[0].line == 7


# -- lock-discipline ---------------------------------------------------------

LOCK_BAD = """\
import threading

class Box:
    _GUARDED_BY = {"items": "_lock"}

    def __init__(self):
        self.items = []
        self._lock = threading.Lock()

    def good(self):
        with self._lock:
            return len(self.items)

    def bad(self):
        return len(self.items)
"""


def test_lock_rule_trips_on_unlocked_access(tmp_path):
    fs = _check(tmp_path, "anywhere/box.py", LOCK_BAD)
    assert _rules_hit(fs) == {"lock-discipline"}
    assert len(fs) == 1
    assert fs[0].line == 15
    assert "Box" in fs[0].message and "_lock" in fs[0].message


def test_lock_rule_init_is_exempt_and_holds_annotation(tmp_path):
    src = """\
import threading

class Box:
    _GUARDED_BY = {"items": "_lock"}

    def __init__(self):
        self.items = []
        self._lock = threading.Lock()

    def flush(self):
        with self._lock:
            self._flush_locked()

    def _flush_locked(self):  # lint: holds(_lock)
        self.items.clear()
"""
    assert _check(tmp_path, "mod.py", src) == []


def test_lock_rule_nested_defs_lose_lock_context(tmp_path):
    src = """\
import threading

class Box:
    _GUARDED_BY = {"items": "_lock"}

    def __init__(self):
        self.items = []
        self._lock = threading.Lock()

    def sneaky(self):
        with self._lock:
            def later():
                return self.items
            return later
"""
    fs = _check(tmp_path, "mod.py", src)
    assert len(fs) == 1                       # the closure runs lock-free
    assert fs[0].rule == "lock-discipline"


def test_lock_rule_rejects_non_literal_registry(tmp_path):
    src = """\
class Box:
    _GUARDED_BY = make_registry()
"""
    fs = _check(tmp_path, "mod.py", src)
    assert len(fs) == 1
    assert "literal" in fs[0].message


# -- pallas-consistency ------------------------------------------------------

PALLAS_HEADER = """\
import jax
from jax.experimental import pallas as pl

def kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]

"""

PALLAS_GOOD = PALLAS_HEADER + """\
def run(x, n_blocks, block_rows, W):
    H = n_blocks * block_rows
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block_rows, W), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), x.dtype),
    )(x)
"""

PALLAS_BAD_GRID = PALLAS_HEADER + """\
def run(x, n_blocks, block_rows, W):
    H = n_blocks * block_rows
    return pl.pallas_call(
        kernel,
        grid=(n_blocks, 2),
        in_specs=[pl.BlockSpec((block_rows, W), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, W), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), x.dtype),
    )(x)
"""

PALLAS_BAD_RANK = PALLAS_HEADER + """\
def run(x, n_blocks, block_rows, W):
    H = n_blocks * block_rows
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block_rows, W), lambda i: (i,))],
        out_specs=pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), x.dtype),
    )(x)
"""

PALLAS_BAD_DIVIDE = PALLAS_HEADER + """\
def run(x):
    return pl.pallas_call(
        kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((3, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((3, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 8), x.dtype),
    )(x)
"""

PALLAS_BAD_OPERANDS = PALLAS_HEADER + """\
def run(x, y):
    return pl.pallas_call(
        kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((4, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((4, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 8), x.dtype),
    )(x, y)
"""


def test_pallas_rule_clean_site_passes(tmp_path):
    assert _check(tmp_path, "kernels/k.py", PALLAS_GOOD) == []


def test_pallas_rule_grid_arity_mismatch(tmp_path):
    fs = _check(tmp_path, "kernels/k.py", PALLAS_BAD_GRID)
    assert _rules_hit(fs) == {"pallas-consistency"}
    assert any("grid has rank 2" in f.message for f in fs)


def test_pallas_rule_block_rank_vs_index_map(tmp_path):
    fs = _check(tmp_path, "kernels/k.py", PALLAS_BAD_RANK)
    assert _rules_hit(fs) == {"pallas-consistency"}
    assert any("returns 1 coordinates" in f.message for f in fs)


def test_pallas_rule_divisibility(tmp_path):
    fs = _check(tmp_path, "kernels/k.py", PALLAS_BAD_DIVIDE)
    assert _rules_hit(fs) == {"pallas-consistency"}
    assert any("does not divide" in f.message for f in fs)


def test_pallas_rule_operand_count(tmp_path):
    fs = _check(tmp_path, "kernels/k.py", PALLAS_BAD_OPERANDS)
    assert _rules_hit(fs) == {"pallas-consistency"}
    assert any("2 operands" in f.message for f in fs)


def test_pallas_rule_resolves_named_specs_and_appends(tmp_path):
    # the spiking_conv_lif idiom: named specs + conditional out_specs.append
    src = PALLAS_HEADER + """\
def run(x, save, n_blocks, block_rows, W):
    H = n_blocks * block_rows
    spec = pl.BlockSpec((block_rows, W), lambda i, j: (i, 0))
    out_specs = [spec]
    out_shape = [jax.ShapeDtypeStruct((H, W), x.dtype)]
    if save:
        out_specs.append(spec)
        out_shape.append(jax.ShapeDtypeStruct((H, W), x.dtype))
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[spec],
        out_specs=out_specs,
        out_shape=out_shape,
    )(x)
"""
    fs = _check(tmp_path, "kernels/k.py", src)
    # the named spec's 2-arg lambda disagrees with the rank-1 grid, and the
    # checker must find it through the name + both appended copies
    assert len(fs) >= 3
    assert _rules_hit(fs) == {"pallas-consistency"}


def test_pallas_rule_resolves_list_concat_and_ifexp(tmp_path):
    # the chunked spiking_conv_lif idiom: the extra save_u output is built
    # as ``[spec] if save else []`` and concatenated onto the base list —
    # the checker must resolve through BOTH the conditional expression and
    # the ``+`` to reach the bad chunk spec (1-arg index map, rank-2 grid)
    src = PALLAS_HEADER + """\
def run(x, save, n_blocks, block_rows, W):
    H = n_blocks * block_rows
    seq_spec = pl.BlockSpec((block_rows, W), lambda i, j: (i, 0))
    bad_chunk_spec = pl.BlockSpec((block_rows, W), lambda i: (i, 0))
    extra_specs = [bad_chunk_spec] if save else []
    extra_shape = [jax.ShapeDtypeStruct((H, W), x.dtype)] if save else []
    return pl.pallas_call(
        kernel,
        grid=(n_blocks, 2),
        in_specs=[seq_spec],
        out_specs=[seq_spec] + extra_specs,
        out_shape=[jax.ShapeDtypeStruct((H, W), x.dtype)] + extra_shape,
    )(x)
"""
    fs = _check(tmp_path, "kernels/k.py", src)
    assert _rules_hit(fs) == {"pallas-consistency"}
    # the good spec passes; only the concatenated conditional one is flagged
    assert len(fs) == 1
    assert "out_specs[1]" in fs[0].message
    assert "takes 1 args but grid has rank 2" in fs[0].message


def test_pallas_rule_concat_and_ifexp_clean_passes(tmp_path):
    # same shape of code with a consistent chunk spec: no findings — the
    # resolution itself must not produce false positives
    src = PALLAS_HEADER + """\
def run(x, save, n_blocks, block_rows, W):
    H = n_blocks * block_rows
    seq_spec = pl.BlockSpec((block_rows, W), lambda i, j: (i, 0))
    extra_specs = [seq_spec] if save else []
    extra_shape = [jax.ShapeDtypeStruct((H, W), x.dtype)] if save else []
    return pl.pallas_call(
        kernel,
        grid=(n_blocks, 2),
        in_specs=[seq_spec],
        out_specs=[seq_spec] + extra_specs,
        out_shape=[jax.ShapeDtypeStruct((H, W), x.dtype)] + extra_shape,
    )(x)
"""
    assert _check(tmp_path, "kernels/k.py", src) == []


PALLAS_MOSAIC_SPECS = """\
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def kernel(c_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...]

def run(counts, x, n_blocks, block_rows, W, G, cb):
    halo = block_rows + 2
    return pl.pallas_call(
        kernel,
        grid=(4, n_blocks, G),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((pl.squeezed, pl.Element(halo), pl.Element(W)),
                         lambda b, i, g: (b, i * block_rows, 0)),
        ],
        out_specs=pl.BlockSpec((pl.squeezed, None, cb, 7),
                               lambda b, i, g: (g, b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, 4, cb, 7), x.dtype),
    )(counts, x)
"""


@pytest.mark.parametrize("mixed", [False, True])
def test_pallas_rule_element_squeezed_and_smem_specs(tmp_path, mixed):
    # the Mosaic-era spec idioms: a whole-array SMEM skip table, an
    # all-Element halo window next to a squeezed batch dim, squeezed output
    # dims (size 1, never a divisibility finding) — clean as written; a
    # halo window mixing Element and plain dims is the one finding
    src = PALLAS_MOSAIC_SPECS
    if mixed:
        src = src.replace("pl.Element(W)", "W")
    fs = _check(tmp_path, "kernels/k.py", src)
    if not mixed:
        assert fs == []
    else:
        assert _rules_hit(fs) == {"pallas-consistency"}
        assert len(fs) == 1 and "in_specs[1]" in fs[0].message
        assert "mixes pl.Element" in fs[0].message


# -- api-hygiene -------------------------------------------------------------

def test_print_ban_inside_repro_package(tmp_path):
    src = 'def f():\n    print("hi")\n'
    fs = _check(tmp_path, "repro/mod.py", src)
    assert _rules_hit(fs) == {"print-ban"}
    # outside the package: no finding
    assert _check(tmp_path, "scripts/mod.py", src) == []


def test_print_ban_suppression(tmp_path):
    src = 'def f():\n    print("artifact")  # lint: allow(print-ban)\n'
    assert _check(tmp_path, "repro/mod.py", src) == []


def test_all_exports_catches_stale_entry(tmp_path):
    src = """\
__all__ = ["real", "ghost"]

def real():
    return 1
"""
    fs = _check(tmp_path, "mod.py", src)
    assert _rules_hit(fs) == {"all-exports"}
    assert "ghost" in fs[0].message


def test_all_exports_accepts_imports_and_conditionals(tmp_path):
    src = """\
import os as real_os
from json import dumps

__all__ = ["real_os", "dumps", "flag", "Late"]

if True:
    flag = 1
else:
    flag = 2

try:
    class Late:
        pass
except ImportError:
    Late = None
"""
    assert _check(tmp_path, "mod.py", src) == []


def test_all_exports_credits_pep562_lazy_table(tmp_path):
    # PEP 562: names routed through a module __getattr__'s literal dict
    # count as bound; a name in neither the bindings nor the table is
    # still a finding
    src = """\
import importlib

__all__ = ["eager", "Lazy"]

_LAZY = {"Lazy": "pkg.sub"}

def eager():
    return 1

def __getattr__(name):
    return getattr(importlib.import_module(_LAZY[name]), name)
"""
    assert _check(tmp_path, "mod.py", src) == []
    fs = _check(tmp_path, "mod.py",
                src.replace('"eager", "Lazy"', '"eager", "Lazy", "ghost"'))
    assert _rules_hit(fs) == {"all-exports"}
    assert "ghost" in fs[0].message


def test_frozen_spec_rejects_mutation(tmp_path):
    src = """\
from dataclasses import dataclass

@dataclass(frozen=True)
class Spec:
    x: int = 0

    def __post_init__(self):
        object.__setattr__(self, "x", max(0, self.x))

    def clamp(self):
        object.__setattr__(self, "x", 1)


def touch(spec):
    object.__setattr__(spec, "x", 2)
"""
    fs = _check(tmp_path, "mod.py", src)
    assert _rules_hit(fs) == {"frozen-spec"}
    assert len(fs) == 2                       # clamp + touch; post_init ok


def test_frozen_spec_rejects_self_assignment(tmp_path):
    src = """\
from dataclasses import dataclass

@dataclass(frozen=True)
class Spec:
    x: int = 0

    def bump(self):
        self.x += 1
"""
    fs = _check(tmp_path, "mod.py", src)
    assert _rules_hit(fs) == {"frozen-spec"}
    assert "dataclasses.replace" in fs[0].message


# -- framework behavior ------------------------------------------------------

def test_parse_error_is_a_finding(tmp_path):
    fs = _check(tmp_path, "repro/broken.py", "def f(:\n")
    assert len(fs) == 1
    assert fs[0].rule == "parse-error"


def test_rule_filter_and_unknown_rule(tmp_path):
    f = tmp_path / "repro" / "mod.py"
    f.parent.mkdir(parents=True)
    f.write_text('print("x")\n')
    assert run_analysis([f], ["clock-discipline"]) == []
    assert len(run_analysis([f], ["print-ban"])) == 1
    with pytest.raises(ValueError, match="unknown rule"):
        run_analysis([f], ["no-such-rule"])


def test_wildcard_suppression(tmp_path):
    src = 'import time\n\ndef f():\n    time.sleep(1)  # lint: allow(*)\n'
    assert _check(tmp_path, "serving/mod.py", src) == []


def test_analyze_file_on_snippet_without_disk():
    sf = SourceFile(Path("repro/virtual.py"), text='print("x")\n')
    registry = rule_registry()
    fs = analyze_file(sf, [registry["print-ban"]])
    assert len(fs) == 1


# -- the real tree is clean (the CI gate) ------------------------------------

def test_repo_tree_is_clean():
    findings = run_analysis([REPO / "src" / "repro", REPO / "tests"])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "repro" / "mod.py"
    bad.parent.mkdir(parents=True)
    bad.write_text('print("x")\n')
    env_path = str(REPO / "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(bad)],
        capture_output=True, text=True, env={"PYTHONPATH": env_path,
                                             "PATH": "/usr/bin:/bin"})
    assert r.returncode == 1
    assert "print-ban" in r.stdout
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--json", str(bad)],
        capture_output=True, text=True, env={"PYTHONPATH": env_path,
                                             "PATH": "/usr/bin:/bin"})
    assert r.returncode == 1
    import json
    data = json.loads(r.stdout)
    assert data[0]["rule"] == "print-ban"
    good = tmp_path / "clean.py"
    good.write_text("x = 1\n")
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(good)],
        capture_output=True, text=True, env={"PYTHONPATH": env_path,
                                             "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0


# -- seeded annotations stay truthful ----------------------------------------

def test_engine_guarded_by_covers_all_three_locks():
    """Meta-test: ServingEngine declares all three of its locks in
    _GUARDED_BY, so the checker actually exercises each one."""
    from repro.serving.engine import ServingEngine

    locks = set(ServingEngine._GUARDED_BY.values())
    assert locks == {"_futures_lock", "_rid_lock", "_submit_lock"}


def test_seeded_registries_exist():
    from repro.obs.trace import TraceRecorder
    from repro.runtime.straggler import StragglerMonitor
    from repro.serving.batcher import DynamicBatcher
    from repro.serving.dispatch import LaneDispatcher
    from repro.serving.futures import RequestHandle
    from repro.serving.metrics import ServingMetrics
    from repro.serving.supervisor import LaneSupervisor

    for cls in (LaneDispatcher, DynamicBatcher, StragglerMonitor,
                LaneSupervisor, TraceRecorder, ServingMetrics):
        assert cls._GUARDED_BY, f"{cls.__name__} lost its registry"
    # RequestHandle is deliberately lock-free (Event-synchronized)
    assert RequestHandle._GUARDED_BY == {}
