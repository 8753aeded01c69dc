"""Child processes of the benchmark modules, held to the CPU.

The mesh sections rehearse on virtual host devices, which only a fresh
process can force, so they run their inner half in a child.  Every child
gets ``JAX_PLATFORMS=cpu``: it is a CPU rehearsal by construction, and a
parent that already ran an in-process section holds the chip, so a child
that reached for it would fail or hang.
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_child_env(devices: int | None = None) -> dict:
    """The parent's environment with JAX held to the CPU, ``devices``
    virtual host devices when given, and ``src/`` plus the repo root on
    ``PYTHONPATH``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices is not None:
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (f"{flags} --xla_force_host_platform_device_count="
                            f"{devices}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH"), REPO)
        if p)
    return env


def spawn_inner(module: str, args, out, *, devices: int) -> int:
    """Run ``python -m <module> --inner <args>`` on ``devices`` virtual CPU
    devices, relay its stdout into ``out`` and return its exit code."""
    proc = subprocess.run(
        [sys.executable, "-m", module, "--inner"] + list(args),
        capture_output=True, text=True, cwd=REPO,
        env=cpu_child_env(devices), timeout=1800)
    print(proc.stdout, end="", file=out)
    if proc.returncode != 0:
        print(f"# FAIL: inner benchmark exited {proc.returncode}: "
              f"{proc.stderr[-2000:]}", file=out)
    return proc.returncode
