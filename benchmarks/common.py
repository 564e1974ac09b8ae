"""Shared timing utilities for the benchmark harness."""
from __future__ import annotations

import json
import time

import jax
import numpy as np

#: every row() call is also collected here so harness entry points can
#: dump a machine-readable artifact next to the CSV stdout (CI uploads
#: benchmarks/*.json)
RESULTS: list = []


def time_fn(fn, *args, warmup: int = 3, iters: int = 20,
            sync=True) -> float:
    """Median wall-clock microseconds per call of a jitted fn."""
    for _ in range(warmup):
        out = fn(*args)
    if sync:
        jax.block_until_ready(out)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        if sync:
            jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(samples))


def row(name: str, us: float, derived: str = ""):
    RESULTS.append({"name": name, "us_per_call": round(us, 2),
                    "derived": derived})
    print(f"{name},{us:.2f},{derived}")


def git_revision() -> dict:
    """``{"commit": <sha>, "dirty": <bool>}`` for the repo this file
    lives in, or ``{}`` when git (or the repo) is unavailable -- a
    benchmark artifact is attributable to a source state, not just a
    machine."""
    import os
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root,
            capture_output=True, text=True, timeout=10,
            check=True).stdout.strip() != ""
    except (OSError, subprocess.SubprocessError):
        return {}
    return {"commit": sha, "dirty": dirty}


def run_metadata() -> dict:
    """Environment stamp for a benchmark artifact, so the perf
    trajectory stays attributable across machines and source states:
    jax/jaxlib versions, backend, device count and kinds, host platform
    and Python, plus the git commit (and dirty flag) the run came
    from."""
    import platform

    import jaxlib

    from repro.core import backend as backend_lib
    devs = jax.devices()
    return {
        **git_revision(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        # the kernel-emission target (repro.core.backend) the run's
        # Pallas calls defaulted to
        "kernel_target": backend_lib.resolve(None).name,
        "device_count": len(devs),
        "device_kinds": sorted({d.device_kind for d in devs}),
        "process_count": jax.process_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }


def dump_json(path: str):
    """Write every row() recorded so far to ``path``:
    ``{"meta": run_metadata(), "rows": [...]}``."""
    with open(path, "w") as f:
        json.dump({"meta": run_metadata(), "rows": RESULTS}, f, indent=1)
    print(f"# wrote {len(RESULTS)} rows to {path}")
