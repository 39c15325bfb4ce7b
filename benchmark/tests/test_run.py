"""The whole command at a tiny size on the CPU (``--rehearse``): the last
line against the contract for every cell, no number under a device metric's
name, and ``correct`` false when the timed path is broken underneath."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
SCALE_DOWN = {"tpcds_store_resident": 1000}


def rehearse(cell, trace, seed=3000000019, seconds=3):
    config = next(w["config"] for w in BENCH["workloads"] if w["name"] == cell)
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace), "--rehearse",
        "--scale-down", str(SCALE_DOWN[config])]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_meets_the_contract(cell, trace):
    line, err = rehearse(cell, trace)
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["attempted"] > 0 and line["failed"] == 0
    # a rehearsal never prints under a device metric's name
    assert line["metrics"] == {} and line["device"]["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in BENCH[group]
                if cell in m.get("workloads", CELLS)}
    got = line["rehearsal_metrics"]
    assert set(got) <= set(declared)
    for name, m in got.items():
        assert m["unit"] == declared[name]["unit"]
        assert isinstance(m["value"], float)
    if trace:
        # no device ran, so what needs the device trace stays silent
        assert not {"device_idle_pct", "scan_roofline"} & set(got)
        assert {"plan_ms", "syncs_per_query", "window_compiles"} <= set(got)
        assert got["window_compiles"]["value"] == 0.0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(got) == set(declared)
    checks = line["checks"]
    assert checks["rows_wrong"] == {"value": 0, "limit": 0}
    assert checks["unanswered"]["value"] == 0
    assert checks["max_rel_err"]["value"] <= checks["max_rel_err"]["limit"]
    for name in checks:
        assert f"check {name}:" in err


def test_no_accelerator_no_result():
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"]
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_outside_a_checkout_of_the_program_nothing_is_printed(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"]
    done = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu",
                                   PYTHONPATH=""), timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""


def alter_double(rows):
    key = next(k for k, v in rows[0].items() if isinstance(v, float))
    rows[0][key] *= 1 + 1e-6


def alter_key(rows):
    key = next(k for k, v in rows[0].items() if isinstance(v, int))
    rows[0][key] += 1


def drop_row(rows):
    rows.pop()


def raise_error(rows):
    raise RuntimeError("the query never answers")


@pytest.mark.parametrize("fault", (alter_double, alter_key, drop_row,
                                   raise_error))
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        fault, monkeypatch, capsys):
    """Drives the rest of a run, past the look for a chip, with the timed
    path broken underneath."""
    from benchmark import run as R
    real = R.load_by_name

    def broken(directory, name):
        module = real(directory, name)
        if directory == "drivers":
            run_one = module.Driver.run_one

            def run_one_broken(self, text):
                out = run_one(self, text)
                if out["rows"]:
                    fault(out["rows"])
                return out
            module.Driver.run_one = run_one_broken
        return module

    monkeypatch.setattr(R, "load_by_name", broken)
    code = R.main(["--workload", "store_scan_agg", "--seed", "3000000019",
                   "--seconds", "2", "--trace", "0", "--rehearse",
                   "--scale-down", "4000"])
    assert code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    bad = [k for k, c in line["checks"].items() if c["value"] > c["limit"]]
    assert set(bad) - {"empty_answers"}


def test_the_window_submits_nothing_after_its_seconds(capsys):
    from benchmark import run as R
    assert R.main(["--workload", "store_scan_agg", "--seed", "11",
                   "--seconds", "2", "--trace", "0", "--rehearse",
                   "--scale-down", "10"]) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    said = json.loads(next(ln for ln in captured.err.splitlines()
                           if ln.startswith('{"cell"')))
    assert line["correct"] is True and said["queries"] == line["attempted"]
    # the rate is what was completed over the time until the last answer
    qps = line["rehearsal_metrics"]["qps"]["value"]
    assert abs(qps - said["queries"] / said["window_s"]) < 1e-9
    assert said["last_submitted_s"] < 2.0 <= said["window_s"]


def test_four_streams_on_a_query_server_run_from_a_traffic_file_alone(
        monkeypatch, capsys):
    """The second driver, which no committed cell uses yet: the committed
    cell's traffic with the served shape in its place."""
    from benchmark import run as R
    real = R.load_json

    def served(*path):
        got = real(*path)
        if path[-2:] == ("workloads", "store_scan_agg.json"):
            got = dict(got, driver="server_streams", streams=4,
                       order="permutation")
        return got

    monkeypatch.setattr(R, "load_json", served)
    assert R.main(["--workload", "store_scan_agg", "--seed", "12",
                   "--seconds", "2", "--trace", "0", "--rehearse",
                   "--scale-down", "4000"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert line["checks"]["rows_wrong"]["value"] == 0
    assert line["checks"]["unanswered"]["value"] == 0


def test_the_control_in_the_programs_place_is_not_correct(capsys):
    from benchmark.tools import readings
    assert readings.main(["--workload", "store_scan_agg", "--seeds", "1,2,3",
                          "--queries", "4", "--scale-down", "10"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert len(lines) == 3 and not any(ln["control_correct"] for ln in lines)
    for ln in lines:
        assert ln["control_float32"]["rows_wrong"] == 0
        assert ln["control_float32"]["max_rel_err"] > 1e-8
