"""chip_smoke.py's phases in-process at a tiny size on the CPU (through the
size arguments of its functions), and its refusal to run without a TPU."""

import json

import pytest

import chip_smoke as S


@pytest.fixture(scope="module")
def sessions():
    return S.make_sessions()


@pytest.fixture(scope="module")
def tpcds(sessions, tmp_path_factory):
    S.register_tpcds(sessions, 1, str(tmp_path_factory.mktemp("tpcds")))
    return sessions


def test_device_phase_refuses_a_stand_in(capsys):
    with pytest.raises(S.SmokeFailure, match="need platform 'tpu'"):
        S.phase_device("tpu")
    assert capsys.readouterr().out == ""
    info = S.phase_device("cpu")
    assert info["platform"] == "cpu" and info["count"] >= 1


def test_main_fails_without_a_tpu(capsys):
    """With platform ``cpu`` the script exits non-zero before any query and
    prints no result line."""
    with pytest.raises(S.SmokeFailure):
        S.main([])
    out = capsys.readouterr().out
    assert '"ok"' not in out and '"phase"' not in out


def test_resident_phase(sessions):
    tpu, cpu = sessions
    out = S.phase_resident(tpu, cpu, n_rows=200_000, parts=4,
                           ref_rows=50_000)
    assert out["rows"] == 200_000 and out["warm_traces"] == 0
    assert out["programs_compiled"] > 0


def test_resident_phase_catches_a_wrong_answer(sessions, monkeypatch):
    tpu, cpu = sessions
    real = S.resident_query
    monkeypatch.setattr(S, "resident_query",
                        lambda df, threshold=0: real(df, threshold=1))
    monkeypatch.setattr(S, "_timed_collect",
                        lambda df: ([{"sk": 1, "sv": 1.0, "sh": 1}], 0.0))
    with pytest.raises(S.SmokeFailure, match="sk 1 != numpy"):
        S.phase_resident(tpu, cpu, n_rows=10_000, parts=2, ref_rows=1_000)


def test_tpcds_phase(tpcds, capsys):
    tpu, cpu = tpcds
    out = S.phase_tpcds(tpu, cpu)
    assert set(out) == set(S.TPCDS_QUERIES)
    for q, r in out.items():
        assert r["rows"] > 0 and r["warm_traces"] == 0, (q, r)
        assert r["host_placed"] == [], (q, r)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["query"] for ln in lines] == list(S.TPCDS_QUERIES)


def test_host_placed_join_fails_the_query(tpcds):
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    tpu, _cpu = tpcds
    tpu.set_conf("spark.rapids.sql.exec.ShuffledHashJoinExec", "false")
    try:
        df = tpu.sql(QUERIES["q3"])
        assert any("Join" in ln for ln in S.host_placed(df))
        with pytest.raises(S.SmokeFailure, match="placed on the host"):
            S.check_device_placement("q3", df)
    finally:
        tpu.set_conf("spark.rapids.sql.exec.ShuffledHashJoinExec", "true")


def test_serving_phase(tpcds):
    tpu, cpu = tpcds
    S.phase_tpcds(tpu, cpu, queries=("q3", "q7"))    # compile first
    out = S.phase_serving(tpu, cpu)
    assert out["queries"] == 4 and out["new_programs"] == 0
    assert all(n > 0 for n in out["rows"].values())


def test_counters_phase(sessions):
    """The phase judges what the smoke added to the process-wide counters,
    so what another test file left in this worker is not its fault, and
    what the smoke's own phases add is."""
    from spark_rapids_tpu.aux import faults
    faults.note_recovery("collective_fallbacks")    # another test's
    baseline = S.hiding_counters()
    out = S.phase_counters({"built": False}, S.CacheCounters(), "unset",
                           baseline)
    assert out["async_failures"] == 0 and out["ledger_errors"] == 0
    assert out["collective_fallbacks"] == 0 and out["recoveries"] == {}
    assert out["transitions"]["h2d_count"] >= 0
    faults.note_recovery("collective_fallbacks")    # the smoke's own
    with pytest.raises(S.SmokeFailure, match="fell back to the host"):
        S.phase_counters({"built": False}, S.CacheCounters(), "unset",
                         baseline)


def test_mesh_phase_on_virtual_devices(tmp_path):
    """The ``--chips 4`` phase on four of the virtual CPU devices: the
    exchange takes the in-mesh path and the shards sit on four devices."""
    from spark_rapids_tpu.parallel.mesh import set_active_mesh
    from spark_rapids_tpu.aux import faults
    tpu, cpu = S.make_sessions(
        S.mesh_conf(4, str(tmp_path / "events.jsonl")))
    try:
        S.register_tpcds((tpu, cpu), 1, str(tmp_path), num_partitions=4,
                         storage="memory")
        # a fallback that another test of this worker left behind is not
        # the phase's: it checks what it adds itself
        faults.note_recovery("collective_fallbacks")
        out = S.phase_mesh(tpu, cpu, 4)
    finally:
        set_active_mesh(None)
    assert len(out["shard_devices"]) == 4
    assert out["ici_exchanges"] > 0 and out["collective_fallbacks"] == 0
