"""CPU tests of the chip benchmark's harness (benchmarks/chip): whole runs
at a small size with the TPU check skipped.

They show that a sound run comes out correct, that the comparison catches
each fault the build cell can have (a built coreset altered where it is
produced, half of a record left out of its build and filled with the mean
of the rest, plain-f32 integral images in place of the compensated ones:
the correctness control, a check that scores a coreset built anew instead
of the one the window timed), and that a configuration, traffic mix and
per-layer metric added as files are found by their names alone.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from yardstick import control, harness  # noqa: E402

SIGNAL = {"kind": "piecewise_dc", "n": 256, "m": 192, "gen_leaves": 8,
          "noise": 0.15, "scale": 2.0, "dc_offset": 100.0}


def _config(name: str, **over) -> dict:
    cfg = {"name": name, "source": "test", "recordcount": 2,
           "signal": dict(SIGNAL), "coreset": {"k": 8, "eps": 0.3},
           "engine_flags": ["--workers", "2"], "chips": 1,
           "limits": {"build_rel_err": 0.3}}
    cfg.update(over)
    return cfg


MIXES = {
    "tiny_build": {"route": "register_build", "loop": "closed", "clients": 2,
                   "record_seed": 5,
                   "check_trees": {"count": 4, "leaves": 8, "fit_share": 0.5,
                                   "label_jitter": 0.3}},
}

DUMMY_READER = '''"""A metric that only the tests define: records the run answered."""


def read(run):
    return float(run.records["ok"].sum())
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> pathlib.Path:
    """A checkout with its own BENCHMARK.json, configurations, mixes and
    one metric of its own; everything else is the benchmark's."""
    r = tmp_path_factory.mktemp("bench_root")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (r / "cfg").mkdir()
    configs = {"tiny_rasters": _config("tiny_rasters"),
               # the control's f32 images lose the loss only at a chip's
               # size; a larger DC level shows it at a size a test holds
               "tiny_dc": _config("tiny_dc", signal=dict(SIGNAL,
                                                         dc_offset=3000.0)),
               "dummy_cfg": _config("dummy_cfg", recordcount=1)}
    for name, cfg in configs.items():
        (r / "cfg" / f"{name}.json").write_text(json.dumps(cfg))
    bench["configs"] = [{"name": n, "source": "test", "file": f"cfg/{n}.json",
                         "reduced": [], "why": "test"} for n in configs]
    cells = [("tiny.build", "tiny_rasters", "tiny_build"),
             ("tiny_dc.build", "tiny_dc", "tiny_build"),
             ("dummy.cell", "dummy_cfg", "dummy_mix")]
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "test"} for n, c, t in cells]
    mixes = dict(MIXES, dummy_mix=MIXES["tiny_build"])
    bench["end_to_end"] = [
        {"name": "ready_p50_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": [n for n, _, _ in cells]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}]
    bench["per_layer"].append({
        "name": "dummy_answered.x", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "SDK + HTTP",
        "moves": "ready_p50_s", "workloads": ["dummy.cell"]})
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    tdir = r / "benchmarks" / "chip" / "traffic"
    tdir.mkdir(parents=True)
    for name, mix in mixes.items():
        (tdir / f"{name}.json").write_text(json.dumps(mix))
    shutil.copytree(BENCH / "metrics", r / "benchmarks" / "chip" / "metrics")
    (r / "benchmarks" / "chip" / "metrics" / "dummy_answered.x.py") \
        .write_text(DUMMY_READER)
    return r


def _run(root, cell, seed, trace=False, hooks=(), seconds=2.5) -> dict:
    return harness.run(cell, seed, seconds, trace, t_start=time.monotonic(),
                       root=root, require_tpu=False, hooks=hooks)


# ------------------------------------------------------- planted faults
def _patch_build(alter):
    """Hook: wrap the engine's band-parallel build so ``alter`` changes
    its input signal or the coreset it returns, where it is produced."""
    def hook(engine):
        from repro.service import engine as engine_mod
        orig = engine_mod.sharded_coreset

        def built(y, k, eps, **kw):
            y, cs = alter(np.array(y, np.float64), None)
            if cs is None:
                cs = orig(y, k, eps, **kw)
                _, cs = alter(None, cs)
            return cs

        engine_mod.sharded_coreset = built

        def undo():
            engine_mod.sharded_coreset = orig
        return undo
    return hook


def _alter_one(y, cs):
    """One block of the built coreset moved by 5 (its support labels and
    its moments alike)."""
    if cs is not None:
        m0, m1, _ = cs.moments[0]
        cs.moments[0, 2] += 10.0 * m1 + 25.0 * m0
        cs.moments[0, 1] += 5.0 * m0
        cs.labels[0] += 5.0
    return y, cs


def _half_batch(y, cs):
    """The second half of the record's rows left out of the build, each
    cell there taking the mean of the first half."""
    if y is not None:
        half = y.shape[0] // 2
        y[half:] = y[:half].mean()
    return y, cs


def _forget_cache(engine):
    """Hook: the engine's cache finds nothing, so the check after the
    window scores a coreset built anew."""
    engine.cache.lookup = lambda *a, **kw: (None, None)


def test_sound_run_is_correct(root):
    res = _run(root, "tiny.build", 11)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["check_rebuilds"]["value"] == 0
    assert list(res["checks"])[-1] == "unanswered"
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", [_alter_one, _half_batch],
                         ids=["answer_altered", "half_batch_left_out"])
def test_planted_fault_comes_out_not_correct(root, fault):
    res = _run(root, "tiny.build", 12, hooks=(_patch_build(fault),))
    assert not res["correct"]
    assert res["checks"]["build_rel_err"]["value"] > \
        res["checks"]["build_rel_err"]["limit"]


def test_control_plain_f32_images_comes_out_not_correct(root):
    sound = _run(root, "tiny_dc.build", 13)
    ctl = _run(root, "tiny_dc.build", 13, hooks=(control.plain_f32_images,))
    assert sound["correct"], sound["checks"]
    assert not ctl["correct"], ctl["checks"]


def _state_tighter_bound(engine):
    """Hook: every dense build states an ``eps_eff`` far below the
    configuration's limit."""
    orig = engine._build_dense

    def build(st, k, eps):
        cs, _, version = orig(st, k, eps)
        return cs, 1e-9, version

    engine._build_dense = build


def test_each_answer_is_held_to_the_bound_it_states(root):
    res = _run(root, "tiny.build", 17, hooks=(_state_tighter_bound,))
    assert res["checks"]["build_rel_err"]["limit"] == 1e-9
    assert not res["correct"]


def test_check_scores_the_coreset_the_window_built(root):
    res = _run(root, "tiny.build", 16, hooks=(_forget_cache,))
    assert not res["correct"]
    assert res["checks"]["check_rebuilds"]["value"] > 0


# --------------------------------------------------------- discovery
def test_new_config_traffic_and_metric_found_by_name(root):
    e2e = _run(root, "dummy.cell", 14)
    assert set(e2e["metrics"]) == {"ready_p50_s", "setup_s"}
    assert all(v["value"] > 0 for v in e2e["metrics"].values())
    traced = _run(root, "dummy.cell", 15, trace=True)
    assert traced["metrics"]["dummy_answered.x"]["value"] == \
        traced["attempted"] - traced["failed"]
    assert traced["metrics"]["dummy_answered.x"]["unit"] == "count"
    assert "busy_s" in traced["device"] and "window_s" in traced["device"]
