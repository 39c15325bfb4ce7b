"""The cell ``served_streams`` (configuration ``tpcds_store_served``: q3
and q55 on four closed-loop streams through one ``QueryServer``) and the
three readers that came with it: the configuration file's contract, each
reader on hand-made input, silent on a program without the spans, and all
the cell's metrics in the last line of a traced rehearsal at the cell's own
scale-down (``test_run.py``'s table of scale-downs cannot take a new
configuration, PERF.md section 7)."""

import json
import os
import subprocess
import sys
import types

import pytest

from conftest import ROOT

from benchmark import run as R
from test_span_metrics import read

CELL = "served_streams"
CONFIG = "tpcds_store_served"
#: a twentieth of SF1, as the tier-1 test of the same cell
#: (``tests/test_served_streams.py``)
SCALE_DOWN = 20
NEW = ("server_wait_ms_per_query", "device_permit_wait_ms_per_query",
       "plan_reuse_pct")
#: what a CPU rehearsal can read of the cell's list: the device's trace
#: and memory statistics (``scan_roofline``, ``device_idle_pct``,
#: ``hbm_peak_gb``, ``join_device_pct``, ``agg_device_pct``) need the chip
REHEARSED = ("plan_ms", "window_compiles", "syncs_per_query",
             "d2h_ms_per_query", "rewrite_ms", "dispatches_per_query",
             "replays_per_query", "pad_factor",
             "probe_gather_rounds_per_query", "sized_joins_per_query") + NEW
NOT_LISTED = ("rollup_rows_padded_per_query", "host_unblocked_ms_per_query")


def load(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def rehearse(trace, seed=3500000011, seconds=3):
    bench = load("BENCHMARK.json")
    cmd = [sys.executable] + bench["command"][1:] + [
        "--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace), "--rehearse", "--scale-down", str(SCALE_DOWN)]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


def test_the_configuration_states_the_deployment_and_its_cut():
    bench = load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["scale_factor"]
    config = load(entry["file"])
    resident = load("benchmark", "configs", "tpcds_store_resident.json")
    # the same data as the one-session deployment
    for key in ("rows", "source_rows", "datagen", "datagen_args",
                "integer_type", "partitions", "session_conf",
                "scale_factor", "source_scale_factor", "fixed_tables"):
        assert config[key] == resident[key], key
    assert config["partitions"] == 1 and config["scale_factor"] == 1
    assert config["source"] == entry["source"] != resident["source"]
    assert "Throughput Test" in config["source"]
    assert "QueryServer" in config["deployment"]
    assert set(config["cuts"]) == {"scale_factor", "what_comes_back"}
    # the guarantees of the one-session deployment, and two of its own
    for key, text in resident["guarantees"].items():
        assert config["guarantees"][key] == text
    assert set(config["guarantees"]) - set(resident["guarantees"]) \
        == {"isolation", "no_stale_or_approximate_answer"}
    assert "exact_repeats_share 0" in \
        config["guarantees"]["no_stale_or_approximate_answer"]
    for key, text in resident["assumed"].items():
        assert config["assumed"][key] == text
    assert set(config["assumed"]) - set(resident["assumed"]) == {
        "throughput_test", "stream_order", "templates",
        "substitution_values"}
    assert "from memory" in config["assumed"]["throughput_test"]


def test_the_serving_defaults_it_states_are_the_programs():
    """The cell sets none of them: a default that changes in
    ``config.py`` changes the deployment, and this says so."""
    from spark_rapids_tpu import config as C
    config = load("benchmark", "configs", CONFIG + ".json")
    stated = {k: v for k, v in config["serving_defaults"].items()
              if k != "note"}
    assert len(stated) == 10
    for key, value in stated.items():
        assert C.registry()[key].default == value, key
        assert key not in config["session_conf"]
    assert stated["spark.rapids.serving.maxConcurrentQueries"] == 4
    assert stated["spark.rapids.sql.concurrentGpuTasks"] == 2


def test_the_cell_and_its_metrics_are_listed_as_the_issue_names_them():
    bench = load("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config=CONFIG, traffic=CELL, chips=1)
    traffic = load("benchmark", "workloads", CELL + ".json")
    assert traffic == dict(traffic, driver="server_streams", streams=4,
                           order="rotation", texts=["q3", "q55"],
                           trace_seconds=10)
    assert bench["run_seconds"] == 40
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "qps"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert [m["name"] for m in bench["per_layer"]][-3:] == list(NEW)
    for name in NOT_LISTED:
        assert CELL not in by_name[name]["workloads"]
    listed = {name for name, m in by_name.items()
              if CELL in m["workloads"]}
    assert listed == set(by_name) - set(NOT_LISTED)
    # the other cells' lists are what they were, the new cell at the end
    for name in listed - set(NEW):
        assert by_name[name]["workloads"] == [
            "store_scan_agg", "store_star_join", CELL]


def served(queue_s, admit_s, permit_s=None):
    phases = {"serve.queue": queue_s, "serve.admit": admit_s,
              "serve.lookup": 0.002, "exec.run": 0.03, "(unattributed)": 0.0}
    if permit_s is not None:
        phases["device.permit"] = permit_s
    return {"phases": phases, "resolved": "planned",
            "plan_cache": "norm_hit"}


def test_the_span_readers_on_hand_made_summaries(monkeypatch):
    from spark_rapids_tpu.aux import tracing
    held = [served(9.0, 9.0, 9.0),          # the warm lap: not the window's
            served(0.001, 0.0005), served(0.003, 0.0015, 0.5)]
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read("server_wait_ms_per_query") == pytest.approx(3.0)
    # a query that never waited for the permit counts 0
    assert read("device_permit_wait_ms_per_query") == pytest.approx(250.0)
    held[2] = served(0.003, 0.0015)
    assert read("device_permit_wait_ms_per_query") == 0.0
    assert isinstance(read("device_permit_wait_ms_per_query"), float)
    # fewer summaries than queries (a result-cache hit runs no query)
    assert read("server_wait_ms_per_query", records=4) is None


@pytest.mark.parametrize("name", NEW[:2])
def test_a_program_without_the_spans_is_silence(monkeypatch, name):
    """The parent of the PR that added the spans: its served queries'
    phases hold neither ``serve.*`` nor ``device.permit``."""
    from spark_rapids_tpu.aux import tracing
    held = [{"phases": {"plan.rewrite": 0.007, "exec.run": 0.03,
                        "xfer.sync": 0.2, "(unattributed)": 0.0},
             "semaphore_wait_s": 0.4}] * 2
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read(name) is None
    monkeypatch.setattr(tracing, "recent_summaries", lambda: [])
    assert read(name) is None


def plan_reuse(before, after):
    run = types.SimpleNamespace(counters={
        "before": {"server": before}, "after": {"server": after}})
    return R.load_by_name("layer_metrics", "plan_reuse_pct").read(run)


def test_plan_reuse_is_the_windows_share_of_lookups_that_found_the_plan():
    zero = {"hits": 0, "norm_hits": 0, "misses": 0, "busy_bypass": 0}
    warm = {"plan_cache": dict(zero, misses=2, norm_hits=0, inserts=2)}
    # 150 lookups in the window: 140 norm hits (counted among the misses),
    # 4 exact hits, 4 cold misses, 2 bypasses of a busy variant
    after = {"plan_cache": dict(zero, hits=4, norm_hits=140, misses=146,
                                busy_bypass=2, inserts=148)}
    assert plan_reuse(warm, after) == pytest.approx(100.0 * 144 / 150)
    # nothing looked up in the window; a driver with no server
    assert plan_reuse(warm, warm) is None
    assert plan_reuse({}, {}) is None
    assert plan_reuse({"plan_cache": {"hits": 1}},
                      {"plan_cache": {"hits": 2}}) is None


def test_a_traced_rehearsal_of_the_cell_prints_what_a_cpu_can_read():
    line, err = rehearse(1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    got = line["rehearsal_metrics"]
    assert set(REHEARSED) <= set(got), sorted(set(REHEARSED) - set(got))
    assert not set(NOT_LISTED) & set(got)
    # a summary counts its own query's work, whoever ran beside it
    assert got["dispatches_per_query"]["value"] == 22.0
    assert got["syncs_per_query"]["value"] == 2.0
    assert got["window_compiles"]["value"] == 0.0
    assert got["replays_per_query"]["value"] == 0.0
    # new literals on a known structure, every query
    assert got["plan_reuse_pct"]["value"] == 100.0
    assert got["server_wait_ms_per_query"]["value"] > 0
    assert got["device_permit_wait_ms_per_query"]["value"] >= 0
    log = json.loads(next(ln for ln in err.splitlines()
                          if ln.startswith('{"cell"')))
    assert log["exact_repeats_share"] == 0
    assert log["server"]["result_cache"]["hits"] == 0
    assert log["server"]["plan_cache"]["hits"] == 0
    assert log["server"]["plan_cache"]["norm_hits"] == log["queries"]
    assert set(log["mean_ms_by_text"]) == {"q3", "q55"}


def test_an_untraced_rehearsal_prints_the_end_to_end_metrics():
    line, _ = rehearse(0, seed=2147483659)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["rehearsal_metrics"]) == {"qps", "setup_s"}
    assert line["metrics"] == {}
