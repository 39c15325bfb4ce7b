"""The cell ``store_sf10_tasks`` (configuration
``tpcds_store_sf10_partitioned``: q3 and q55 on one closed-loop session over
the SF10 store channel in ten partitions) and the six readers that came
with it: the configuration and the traffic say what ISSUE 37 names, each
reader on hand-made input, silent on a program without the span or counter,
and all the cell's metrics that a CPU can read in the last line of a traced
rehearsal at a scale-down of 20 (``test_run.py``'s table of scale-downs
cannot take a new configuration, PERF.md section 7)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

from test_span_metrics import read

CELL = "store_sf10_tasks"
CONFIG = "tpcds_store_sf10_partitioned"
SCALE_DOWN = 20
NEW = ("tasks_per_query", "task_permit_wait_ms_per_query",
       "broadcast_builds_per_query", "exchange_ms_per_query",
       "exchange_pad_factor", "exchange_device_pct")
#: the seventeen lists of a one-session cell that the cell was appended to
SEVENTEEN = ("plan_ms", "window_compiles", "syncs_per_query",
             "d2h_ms_per_query", "scan_roofline", "device_idle_pct",
             "hbm_peak_gb", "rewrite_ms", "host_unblocked_ms_per_query",
             "dispatches_per_query", "replays_per_query", "pad_factor",
             "join_device_pct", "agg_device_pct",
             "probe_gather_rounds_per_query", "sized_joins_per_query",
             "sized_stages_per_query")
#: what a CPU rehearsal cannot read: the device's trace and memory
DEVICE_ONLY = ("scan_roofline", "device_idle_pct", "hbm_peak_gb",
               "join_device_pct", "agg_device_pct", "exchange_device_pct")


def load(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def test_the_configuration_is_the_sources_own_scale_in_sparks_layout():
    bench = load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == []
    config = load(entry["file"])
    resident = load("benchmark", "configs", "tpcds_store_resident.json")
    assert config["source"] == entry["source"]
    for word in ("store channel", "Power Test", "query3.tpl", "query55.tpl",
                 "SF10", "128 MB", "10 MB"):
        assert word in config["source"], word
    # table 3-2 at SF10, nothing cut, ten partitions
    assert config["rows"] == resident["source_rows"] \
        == config["source_rows"]
    assert config["rows"]["store_sales"] == 28800991
    assert config["scale_factor"] == config["source_scale_factor"] == 10
    assert config["partitions"] == 10
    for key in ("datagen", "datagen_args", "integer_type", "fixed_tables",
                "guarantees"):
        assert config[key] == resident[key], key
    for key, text in resident["assumed"].items():
        if key != "storage":
            assert config["assumed"][key] == text
    assert set(config["assumed"]) - set(resident["assumed"]) == {
        "split_count", "spark_defaults", "every_table_split"}
    assert "Parquet splits" in config["assumed"]["storage"]
    assert "as recalled" in config["assumed"]["split_count"]
    assert "taskParallelism" in config["deployment"]
    assert "concurrentGpuTasks" in config["deployment"]


def test_the_three_defaults_it_rests_on_are_the_programs():
    """Stated by key and value; ``taskParallelism`` 4 is what the default
    (0: auto) resolves to on a host with four cores or more."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.plan import base
    conf = load("benchmark", "configs", CONFIG + ".json")["session_conf"]
    assert conf == {"spark.rapids.sql.enabled": "true",
                    "spark.sql.autoBroadcastJoinThreshold": "10485760",
                    "spark.rapids.sql.concurrentGpuTasks": "2",
                    "spark.rapids.tpu.taskParallelism": "4"}
    registry = C.registry()
    assert registry["spark.sql.autoBroadcastJoinThreshold"].default \
        == "10485760"
    assert registry["spark.rapids.sql.concurrentGpuTasks"].default == 2
    assert registry["spark.rapids.tpu.taskParallelism"].default == 0
    assert C.TpuConf(conf).get(
        "spark.sql.autoBroadcastJoinThreshold") == 10 * 1024 * 1024
    if (os.cpu_count() or 1) >= 4:
        base.set_task_parallelism(0)
        assert base.effective_task_parallelism() == 4


def test_the_cell_and_its_metrics_are_listed_as_the_issue_names_them():
    bench = load("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config=CONFIG, traffic=CELL, chips=1)
    assert bench["workloads"][-1] == entry
    traffic = load("benchmark", "workloads", CELL + ".json")
    assert traffic == dict(traffic, driver="session_loop", streams=1,
                           order="rotation", texts=["q3", "q55"],
                           trace_seconds=20)
    assert bench["run_seconds"] == 40
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "qps"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(NEW)
    assert {by_name[n]["layer"] for n in NEW[3:]} \
        == {"exchange exec/exchange.py"}
    for name in SEVENTEEN:
        assert by_name[name]["workloads"][-1] == CELL, name
    listed = {name for name, m in by_name.items() if CELL in m["workloads"]}
    assert listed == set(SEVENTEEN) | set(NEW)


def tasked(tasks, permit_s, write_s, read_s, rows, padded, builds=2,
           exchanges=1):
    phases = {"exec.run": 0.01, "task.run": 0.5, "(unattributed)": 0.0,
              "exchange.write": write_s, "exchange.read": read_s}
    if permit_s is not None:
        phases["device.permit"] = permit_s
    return {"phases": phases, "tasks": tasks, "broadcast_builds": builds,
            "exchanges": exchanges, "exchange_rows": rows,
            "exchange_rows_padded": padded}


def test_the_readers_on_hand_made_summaries(monkeypatch):
    from spark_rapids_tpu.aux import tracing
    held = [tasked(99, 9.0, 9.0, 9.0, 9, 9),        # the warm lap
            tasked(12, 0.25, 0.03, 0.001, 300, 3276800),
            tasked(11, None, 0.01, 0.003, 100, 3276800, exchanges=2)]
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read("tasks_per_query") == 11.5
    # a query whose tasks never waited for the permit counts 0
    assert read("task_permit_wait_ms_per_query") == pytest.approx(125.0)
    assert read("broadcast_builds_per_query") == 2.0
    assert read("exchange_ms_per_query") == pytest.approx(22.0)
    # sums over the window, not a mean of ratios
    assert read("exchange_pad_factor") == pytest.approx(6553600 / 400)
    for name in NEW[:5]:
        assert isinstance(read(name), float)
        # fewer summaries than queries
        assert read(name, records=4) is None
    # a window that exchanged no row has no factor
    held[1:] = [tasked(1, None, 0.0, 0.0, 0, 0, exchanges=0)] * 2
    assert read("exchange_pad_factor") is None
    assert read("exchange_ms_per_query") == 0.0


@pytest.mark.parametrize("name", NEW[1:5])
def test_a_program_without_the_span_or_counter_is_silence(monkeypatch, name):
    """The parent of the PR that added them: its summaries count ``tasks``
    and open ``device.permit``, and hold nothing else of this."""
    from spark_rapids_tpu.aux import tracing
    held = [{"phases": {"exec.run": 0.03, "device.permit": 0.2,
                        "(unattributed)": 0.0},
             "tasks": 34, "semaphore_wait_s": 0.4}] * 2
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read(name) is None
    assert read("tasks_per_query") == 34.0
    monkeypatch.setattr(tracing, "recent_summaries", lambda: [])
    assert read(name) is None


def test_exchange_device_pct_on_a_hand_made_trace():
    trace = {"programs": [["join.pair", 6.0], ["exchange.split", 1.5],
                          ["exchange.pid", 0.5], ["batch.concat", 2.0]]}
    assert read("exchange_device_pct", trace=trace) == pytest.approx(20.0)
    # the parent dispatches the same work as batch.compact and eager ops
    parent = {"programs": [["join.pair", 6.0], ["batch.compact", 2.0]]}
    assert read("exchange_device_pct", trace=parent) is None
    assert read("exchange_device_pct", trace={"programs": []}) is None
    assert read("exchange_device_pct", trace=None) is None


def test_a_traced_rehearsal_of_the_cell_prints_what_a_cpu_can_read():
    bench = load("BENCHMARK.json")
    cmd = [sys.executable] + bench["command"][1:] + [
        "--workload", CELL, "--seed", "3700000019", "--seconds", "3",
        "--trace", "1", "--rehearse", "--scale-down", str(SCALE_DOWN)]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"] == {}
    got = line["rehearsal_metrics"]
    want = (set(SEVENTEEN) | set(NEW)) - set(DEVICE_ONLY)
    assert want <= set(got), sorted(want - set(got))
    assert got["broadcast_builds_per_query"]["value"] == 2.0
    assert got["replays_per_query"]["value"] == 0.0
    # ten map tasks, and the reduce partitions the adaptive reader left
    assert 11 <= got["tasks_per_query"]["value"] <= 12
    assert got["exchange_pad_factor"]["value"] > 100
    assert got["exchange_ms_per_query"]["value"] > 0
    log = json.loads(next(ln for ln in done.stderr.splitlines()
                          if ln.startswith('{"cell"')))
    assert log["exact_repeats_share"] == 0
    assert set(log["mean_ms_by_text"]) == {"q3", "q55"}
