"""CPU checks of the benchmark's yardstick: names resolve, the copied
generators and frozen profiles match the program, the roofline work
function, the trace reduction on a trace recorded on a TPU v5e, and the
harness's refusal to run without a TPU.  No test describes a topology or
needs a chip."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

from bench import harness as H  # noqa: E402
from bench import roofline as R  # noqa: E402
from bench import trace as TR  # noqa: E402
from bench import traffic as T  # noqa: E402


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("cell", [w["name"] for w in bench_json()["workloads"]])
def test_cell_resolves_by_name(cell):
    b = bench_json()
    c = H.load_cell(cell, ROOT)
    w = c["workload"]
    assert os.path.exists(os.path.join(ROOT, "bench", "traffic", f"{w['traffic']}.json"))
    assert os.path.exists(os.path.join(ROOT, "bench", "cells", f"{cell}.json"))
    assert c["shapes"], "the cell file lists its kernel shape buckets"
    assert {m["name"] for m in c["end_to_end"]} >= {"events_per_s", "setup_s"}
    assert c["per_layer"], "every cell reports per-layer metrics"
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(H.reader(m["name"]))
    nodes = T.nodes(c["config"])
    assert len(nodes) == c["config"]["nodes"]
    assert all(n["chip"] in c["profiles"] for n in nodes)
    assert set(c["apps"]) == set(c["profiles"][nodes[0]["chip"]])


def test_metric_workloads_key_limits_its_cells(tmp_path):
    """A metric entry that lists ``workloads`` is reported in those cells
    alone; one without the key in every cell that reports what it moves."""
    b = bench_json()
    cells = [w["name"] for w in b["workloads"]]
    b["end_to_end"].append({"name": "only_first_e2e", "unit": "s", "better": "lower",
                            "bound": 0.1, "source": "host_clock",
                            "workloads": cells[:1]})
    b["per_layer"].append({"name": "only_first", "unit": "us", "better": "lower",
                           "source": "program_span", "layer": "dispatch",
                           "moves": "events_per_s", "workloads": cells[:1]})
    b["per_layer"].append({"name": "under_first_e2e", "unit": "us", "better": "lower",
                           "source": "program_span", "layer": "dispatch",
                           "moves": "only_first_e2e"})
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    for i, cell in enumerate(cells):
        c = H.load_cell(cell, str(tmp_path))
        e2e = {m["name"] for m in c["end_to_end"]}
        per_layer = {m["name"] for m in c["per_layer"]}
        assert ("only_first_e2e" in e2e) == (i == 0)
        assert ("only_first" in per_layer) == (i == 0)
        assert ("under_first_e2e" in per_layer) == (i == 0)
        assert per_layer >= {m["name"] for m in bench_json()["per_layer"]}


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_streams_match_program_generators(seed):
    from repro.core import arrivals

    apps = [f"app{i}" for i in range(8)]
    got = T.poisson_stream(apps, rate=4.8, n=300, seed=seed)
    want = arrivals.poisson_stream(apps, rate=4.8, n=300, seed=seed)
    assert got == [(a.t, a.name, a.app) for a in want]
    got = T.bursty_stream(apps, rate=2.4, n=300, burst=16, seed=seed)
    want = arrivals.bursty_stream(apps, rate=2.4, n=300, burst=16, seed=seed)
    assert got == [(a.t, a.name, a.app) for a in want]


@pytest.mark.parametrize("family", ["three_family", "anchor_grow"])
def test_app_families_match_fleet_bench(family):
    from benchmarks import bench_fleet as bf
    from repro.roofline.hw import CHIPS

    cfg = H.load_cell("fleet256.poisson", ROOT)["config"]
    make = {"three_family": bf.synth_apps, "anchor_grow": bf.synth_elastic_apps}[family]
    seed = {"three_family": 3, "anchor_grow": 5}[family]
    for chip in cfg["chip_cycle"]:
        want = make(CHIPS[chip], seed=seed)
        got = T.FAMILIES[family](cfg["chip_slow"][chip], 8, seed)
        for app, prof in want.items():
            assert {int(k): v for k, v in got[app]["runtime"].items()} == prof.runtime
            assert {int(k): v for k, v in got[app]["busy_power"].items()} == prof.busy_power


def test_frozen_dvfs_profiles_match_calibration():
    from repro.core import calibration as C
    from repro.roofline.hw import CHIPS

    cfg = H.load_cell("dvfs512.burst", ROOT)["config"]
    assert cfg["apps"] == list(C.APP_ORDER)
    for chip, apps in cfg["profiles"].items():
        assert cfg["idle_w"][chip] == CHIPS[chip].power_idle
        truth = C.build_system(chip, freq_levels=4)
        for app, p in apps.items():
            want = truth[app]
            for key, attr in (("runtime", "runtime"), ("busy_power", "busy_power"),
                              ("dram_util", "dram_util"), ("freq_time", "freq_time"),
                              ("freq_power", "freq_power")):
                assert {int(k): v for k, v in p[key].items()} == getattr(want, attr)


def test_roofline_work_on_hand_computed_shapes():
    # 10 rows x 2 slots, dev and g planes, the n column: per row 2*2 plane
    # adds + 7 combine ops; 4 bytes x (2*2 plane + 1 column + 2 outputs)
    req = dict(dev=np.zeros((10, 2)), g=np.zeros((10, 2)), n=np.ones(10))
    assert R.request_work(req) == (110.0, 280.0)
    # 3 x 4 with f, bias and mask: 3*4 adds + 7 + 3 (frequency) + 1 (bias)
    # ops a row; 4 bytes x (3*4 + 3 columns + 2 outputs)
    req = dict(dev=np.zeros((3, 4)), g=np.zeros((3, 4)), n=np.ones(3),
               f=np.zeros((3, 4)), bias=np.zeros(3), mask=np.ones(3))
    assert R.request_work(req) == (69.0, 204.0)
    peak = R.peaks("TPU v5 lite")
    assert R.least_seconds(69.0, 204.0, peak) == 204.0 / 819e9
    with pytest.raises(KeyError):
        R.peaks("no such chip")


def test_trace_reduction_on_recorded_chip_trace(tmp_path):
    shutil.copytree(os.path.join(DATA, "v5e_trace"), tmp_path / "tr")
    got = TR.reduce(str(tmp_path / "tr"))
    want = json.load(open(os.path.join(DATA, "v5e_trace_expected.json")))
    assert got["devices"] == 1
    for k in ("busy_s", "window_s", "kernel_device_s"):
        assert got[k] == pytest.approx(want[k], rel=1e-12, abs=0.0)
    assert 0.0 < got["kernel_device_s"] <= got["busy_s"] < got["window_s"]
    idle = dict((k, v) for k, v in got["idle_by_host_span"])
    assert sum(idle.values()) == pytest.approx(got["window_s"] - got["busy_s"])
    assert idle == pytest.approx(dict(want["idle_by_host_span"]))
    assert len(got["breakdown"]["device_ops"]) <= 10


def test_union_and_idle_attribution_by_hand():
    busy = TR.union([(0, 10), (5, 20), (30, 40)])
    assert busy == [(0, 20), (30, 40)]
    host = [(0, 100, "window"), (18, 35, "instant"), (21, 29, "kernel")]
    assert TR.innermost(host) == [(0, 18, "window"), (18, 21, "instant"),
                                  (21, 29, "kernel"), (29, 35, "instant"),
                                  (35, 100, "window")]
    gaps = TR.idle_by_span(busy, 0, 100, host)
    assert gaps == [("instant", 1), ("kernel", 8), ("instant", 1), ("window", 60)]
    # idle time past every span is still counted
    assert TR.idle_by_span([], 0, 10, [(2, 4, "route")]) == [
        ("route", 2), ("outside any span", 8)]


def run_bench(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fleet256.poisson",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_refuses_without_tpu():
    p = run_bench(ROOT)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not p.stdout.strip()


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in bench_json()["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(str(tmp_path))
    assert p.returncode != 0
    assert not p.stdout.strip()
