"""Shuffle worker process (executor analog for cross-process tests).

Each worker owns a ShuffleBufferCatalog + ShuffleServer + ShuffleClient
over a SocketTransport, and is driven by pickled commands on a
multiprocessing Pipe from the driver (the reference's executor receives
work over Spark RPC; the control channel is stand-in driver RPC, the DATA
plane is the real socket transport between workers):

  ("peers", {executor_id: (host, port)})       update peer table
  ("load", shuffle_id, map_id, partition, n_rows, seed)
                                               generate + register blocks
  ("fetch", peer_id, shuffle_id, partition)    fetch over the socket;
                                               replies ("ok", rows, ksum)
                                               or ("fetch_failed", why)
  ("chaos", point, n, skip)                    arm a fault point inside
                                               the worker (aux/faults.py)
  ("exit",)                                    shut down

The worker heartbeats ("hb", executor_id) over the pipe every 0.2s; the
driver feeds these into ShuffleHeartbeatManager (liveness detection of a
killed worker = heartbeat expiry, reference
RapidsShuffleHeartbeatManager.scala).
"""

from __future__ import annotations

import os
import threading
import time


def run_worker(executor_id: str, port: int, ctrl) -> None:
    # workers never touch the device: the shuffle data plane is host-side,
    # and a child process that reached for the chip would take it from (or
    # hang on) the parent that holds it
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from spark_rapids_tpu.columnar.batch import batch_from_pydict
    from spark_rapids_tpu.shuffle.catalog import (ShuffleBlockId,
                                                  ShuffleBufferCatalog,
                                                  ShuffleReceivedBufferCatalog)
    from spark_rapids_tpu.shuffle.client_server import (FetchRetryPolicy,
                                                        ShuffleClient,
                                                        ShuffleServer)
    from spark_rapids_tpu.shuffle.socket_transport import SocketTransport

    transport = SocketTransport(executor_id, port=port)
    catalog = ShuffleBufferCatalog()
    received = ShuffleReceivedBufferCatalog()
    server = ShuffleServer(executor_id, catalog, transport)
    # short per-attempt timeout + tight backoff: a dead peer must surface
    # as fetch_failed well inside the test harness timeout
    client = ShuffleClient(executor_id, transport, received,
                           retry=FetchRetryPolicy(timeout_s=10.0,
                                                  max_retries=1,
                                                  base_wait_s=0.05,
                                                  max_wait_s=0.2))
    transport.set_handlers(server, client)

    stop = threading.Event()

    def heartbeats():
        while not stop.is_set():
            try:
                ctrl.send(("hb", executor_id, transport.endpoint))
            except (BrokenPipeError, OSError):
                return
            stop.wait(0.2)

    threading.Thread(target=heartbeats, daemon=True).start()
    ctrl.send(("ready", executor_id, transport.endpoint))

    while True:
        cmd = ctrl.recv()
        kind = cmd[0]
        if kind == "exit":
            stop.set()
            transport.shutdown()
            ctrl.send(("bye",))
            return
        if kind == "peers":
            for pid, (host, pport) in cmd[1].items():
                transport.update_peer(pid, host, pport)
            ctrl.send(("peers_ok",))
        elif kind == "load":
            _sid, _mid, _pid, n_rows, seed = cmd[1:]
            rng = np.random.default_rng(seed)
            hb = batch_from_pydict({
                "k": rng.integers(0, 1000, n_rows).astype(np.int64),
                "v": np.round(rng.standard_normal(n_rows), 6),
                "s": np.array([f"row{i}" for i in range(n_rows)],
                              dtype=object),
            })
            # two frames per block exercises frame reassembly
            half = n_rows // 2
            blk = ShuffleBlockId(_sid, _mid, _pid)
            catalog.add_batch(blk, hb.slice(0, half))
            catalog.add_batch(blk, hb.slice(half, n_rows - half))
            ksum = int(np.sum(np.asarray(hb.columns[0].arrow)))
            # observability hook: routes to any sink the worker process
            # registered (aux.events global sinks); otherwise free
            from spark_rapids_tpu.aux.events import emit
            emit("shuffleBlockLoaded", executor_id=executor_id,
                 shuffle_id=_sid, map_id=_mid, partition=_pid,
                 rows=n_rows)
            ctrl.send(("loaded", n_rows, ksum))
        elif kind == "chaos":
            from spark_rapids_tpu.aux import faults
            _point, _n, _skip = cmd[1:]
            exc = faults.CHAOS_POINTS.get(_point, (None, None))[1]
            faults.arm_fault(_point, _n, _skip, exc)
            ctrl.send(("chaos_ok", _point))
        elif kind == "fetch":
            peer_id, sid, pid = cmd[1:]
            try:
                blocks = client.do_fetch(peer_id, sid, pid)
                rows = 0
                ksum = 0
                for b in blocks:
                    for hb in received.read_batches(b):
                        rows += hb.row_count
                        ksum += int(np.sum(np.asarray(
                            hb.columns[0].arrow)))
                    received.drop(b)
                from spark_rapids_tpu.aux.events import emit
                emit("shuffleWorkerFetch", executor_id=executor_id,
                     peer=peer_id, shuffle_id=sid, partition=pid,
                     rows=rows)
                ctrl.send(("ok", rows, ksum))
            except Exception as e:    # noqa: BLE001 - fetch failure signal
                ctrl.send(("fetch_failed",
                           f"{type(e).__name__}: {e}"))
        else:
            ctrl.send(("error", f"unknown command {kind!r}"))
