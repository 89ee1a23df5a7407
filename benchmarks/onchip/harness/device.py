"""The chip: finding it, its peaks, its memory, and what compiles."""

from __future__ import annotations

import json
from pathlib import Path

import jax

HERE = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    pass


def require_chips(n: int) -> dict:
    """The device record of the result line; raises NoChip unless JAX
    sees at least `n` TPU chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"this cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return describe(n)


def describe(n: int) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": n}


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def memory_peak_bytes(n: int) -> int:
    """Peak bytes in use on the fullest of the first `n` devices."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:n])


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at one fixed directory of the checkout,
    holding every program, however quick its compile."""
    path = root / ".jax_cache" / "onchip"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


class CompileCounter:
    """Counts backend compiles from JAX's monitoring events while
    `armed`: a compile inside the measured window is a fault."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.armed = False
        self.count = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == self.EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))
