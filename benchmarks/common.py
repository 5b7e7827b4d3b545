"""Shared benchmark utilities: timing, structured rows, roofline columns.

Section modules yield ``Row`` objects — structured records with a name, the
measured µs/call, and a ``derived`` dict of typed extras. CSV is only a
*rendering* (``Row.render()``/``Row.parse()``), so ``run.py --json`` can
record the real values instead of re-parsing its own printout (the old
``line.split(",", 2)`` silently mis-parsed any non-CSV output line).

``bw_fields`` attaches the roofline columns — achieved GB/s against the
device's published HBM ceiling (``launch/roofline.PEAKS``; a device
without published peaks, such as a CPU, gets no ceiling) — and
``env_metadata`` captures the environment block every BENCH_*.json needs to
stay interpretable (backend, devices, jax version, git sha, timestamp).
"""
from __future__ import annotations

import dataclasses
import subprocess
import time
from typing import Callable, Dict, Optional

import jax


def time_fn(fn: Callable, *args, repeats: int = 5, warmup: int = 2) -> float:
    """Median wall time per call in microseconds (jit'd, blocked)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


# --------------------------------------------------------------------------
# structured rows
# --------------------------------------------------------------------------

HEADER = "name,us_per_call,derived"


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.2f}"
    return str(v)


def _parse_val(s: str):
    for cast in (int, float):
        try:
            return cast(s)
        except ValueError:
            pass
    if s in ("True", "False"):
        return s == "True"
    return s


@dataclasses.dataclass
class Row:
    """One benchmark measurement: section modules yield these; CSV/JSON are
    renderings of the same record."""
    name: str
    us: float
    derived: Dict[str, object] = dataclasses.field(default_factory=dict)

    def render(self) -> str:
        d = ";".join(f"{k}={_fmt(v)}" for k, v in self.derived.items())
        return f"{self.name},{self.us:.1f},{d}"

    @classmethod
    def parse(cls, line: str) -> "Row":
        """Strict inverse of ``render`` (for subprocess-emitted sections).
        Raises ``ValueError`` naming the offending line instead of silently
        mangling it."""
        parts = line.split(",", 2)
        if len(parts) != 3:
            raise ValueError(
                f"malformed benchmark row (want 'name,us,derived'): {line!r}")
        name, us_s, d = parts
        try:
            us = float(us_s)
        except ValueError:
            raise ValueError(
                f"malformed benchmark row (us_per_call {us_s!r} is not a "
                f"number): {line!r}") from None
        derived = {}
        for item in d.split(";"):
            if not item:
                continue
            if "=" not in item:
                raise ValueError(
                    f"malformed derived field {item!r} (want k=v): {line!r}")
            k, v = item.split("=", 1)
            derived[k] = _parse_val(v)
        return cls(name, us, derived)

    def to_record(self, section: str) -> dict:
        return {"section": section, "name": self.name,
                "us_per_call": self.us, "derived": dict(self.derived)}


def row(name: str, us: float, **derived) -> Row:
    return Row(name, us, derived)


# --------------------------------------------------------------------------
# roofline columns
# --------------------------------------------------------------------------

def bw_fields(n_bytes: float, us: float) -> Dict[str, float]:
    """Roofline accounting for a row that streams ``n_bytes``: achieved GB/s
    and, on a device with published peaks (``launch/roofline.PEAKS``), its
    HBM ceiling and the fraction of it reached. A device without peaks (a
    CPU) gets no roofline columns."""
    from repro.launch.roofline import PEAKS, device_kind
    kind = device_kind()
    gbps = n_bytes / us / 1e3 if us > 0 else 0.0   # bytes/µs -> GB/s
    out = {"gbps": round(gbps, 3)}
    if kind in PEAKS:
        roof = PEAKS[kind].hbm_bw / 1e9
        out.update(roof_gbps=round(roof, 1), roof_frac=round(gbps / roof, 4))
    return out


# --------------------------------------------------------------------------
# environment metadata for --json trajectories
# --------------------------------------------------------------------------

def _git_sha() -> str:
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=here, capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:
        return os.environ.get("GIT_SHA", "unknown")


def env_metadata(timestamp: Optional[str] = None) -> dict:
    """The block that makes a BENCH_*.json interpretable later: backend,
    device count/kind, versions, git sha, and the runner's timestamp."""
    import platform
    devs = jax.devices()
    meta = {
        "backend": jax.default_backend(),
        "device_count": len(devs),
        "device_kind": devs[0].device_kind if devs else "none",
        "jax_version": jax.__version__,
        "python_version": platform.python_version(),
        "platform": platform.platform(),
        "git_sha": _git_sha(),
    }
    if timestamp:
        meta["timestamp"] = timestamp
    return meta
