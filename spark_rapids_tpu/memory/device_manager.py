"""Device/runtime initialization (reference: GpuDeviceManager.scala:37
initializeGpuAndMemory — device acquisition, RMM pool modes, pinned pool,
store wiring; Plugin.scala:502 executor init sequence).

Here: detect the jax device, size the accounting pool from HBM (or conf
override for tests), wire the BufferCatalog tiers and the TpuSemaphore, and
enforce x64 mode.  ``initialize()`` is idempotent; ``shutdown()`` tears down
(reference executor plugin shutdown).
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from spark_rapids_tpu import config as C
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.memory.catalog import BufferCatalog
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.memory.metrics import MetricsRegistry

log = logging.getLogger(__name__)

_runtime_lock = threading.Lock()
_runtime: Optional["DeviceManager"] = None


class DeviceManager:
    def __init__(self, conf: TpuConf):
        import jax
        jax.config.update("jax_enable_x64", True)
        self.conf = conf
        self.device = jax.devices()[0]
        pool_override = conf.get(C.DEVICE_POOL_SIZE.key)
        if pool_override:
            pool_bytes = pool_override
        else:
            pool_bytes = self._detect_hbm_bytes(self.device)
            pool_bytes = int(pool_bytes * conf.get(C.DEVICE_POOL_FRACTION.key))
        spill_dir = conf.get(C.SPILL_TO_DISK_DIR.key) or None
        self.catalog = BufferCatalog(
            device_limit_bytes=pool_bytes,
            host_limit_bytes=conf.get(C.HOST_SPILL_STORAGE_SIZE.key),
            disk_dir=spill_dir,
            debug=conf.get(C.RMM_DEBUG.key))
        self.semaphore = TpuSemaphore(conf.get(C.CONCURRENT_TPU_TASKS.key))
        self.metrics = MetricsRegistry()
        log.info("DeviceManager initialized on %s pool=%dMiB",
                 self.device, pool_bytes >> 20)

    @staticmethod
    def _detect_hbm_bytes(device) -> int:
        """HBM capacity via PJRT memory stats (reference: Cuda.memGetInfo in
        GpuDeviceManager).  CPU devices report none and get a pretend
        4 GiB pool; an accelerator that cannot say how much memory it has
        is an error, not a default."""
        stats = device.memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
        if device.platform != "cpu":
            raise RuntimeError(
                f"{device.platform} device {device} reports no bytes_limit "
                f"(memory_stats={stats!r}); set "
                f"{C.DEVICE_POOL_SIZE.key} to size the pool explicitly")
        return 4 << 30

    def shutdown(self) -> None:
        self.catalog.close()


def initialize(conf: Optional[TpuConf] = None) -> DeviceManager:
    """Idempotent runtime init (reference: GpuDeviceManager.initializeGpuAndMemory
    called from RapidsExecutorPlugin.init, Plugin.scala:548)."""
    global _runtime
    with _runtime_lock:
        if _runtime is None:
            _runtime = DeviceManager(conf or C.default_conf())
        elif conf is not None and conf is not _runtime.conf:
            # device/memory settings are startup-scoped (reference: RapidsConf
            # STARTUP level); a second session cannot re-shape the pool
            for key in (C.DEVICE_POOL_SIZE.key, C.DEVICE_POOL_FRACTION.key,
                        C.HOST_SPILL_STORAGE_SIZE.key, C.SPILL_TO_DISK_DIR.key,
                        C.CONCURRENT_TPU_TASKS.key):
                if conf.get(key) != _runtime.conf.get(key):
                    log.warning(
                        "runtime already initialized; startup conf %s=%r is "
                        "ignored (active value %r). Call shutdown() first to "
                        "re-shape the device runtime.", key, conf.get(key),
                        _runtime.conf.get(key))
        return _runtime


def get_runtime() -> Optional[DeviceManager]:
    return _runtime


def free_device_headroom(divisor: int) -> Optional[int]:
    """Free device-pool bytes divided by a safety factor, or None when no
    runtime is initialized (tests driving execs directly).  The single
    policy point for every out-of-core trigger (agg merge, external sort,
    running window, exchange store)."""
    rt = get_runtime()
    if rt is None:
        return None
    free = max(0, rt.catalog.device_limit - rt.catalog.device_bytes)
    return free // divisor


def shutdown() -> None:
    global _runtime
    with _runtime_lock:
        if _runtime is not None:
            _runtime.shutdown()
            _runtime = None
