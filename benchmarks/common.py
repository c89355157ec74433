"""Shared benchmark helpers."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from datetime import datetime, timezone
from pathlib import Path

OUT = Path("experiments")
SRC = Path(__file__).resolve().parent.parent / "src"

#: un-committed runtime output (profiler captures, quick dry-runs,
#: trajectory reports) — ``.gitignore``'s ``experiments/*`` rule keeps
#: everything under here out of the repo; only the schema-stamped
#: ``experiments/*.json`` artifacts are tracked
RUNTIME_OUT = OUT / "runtime"


def runtime_dir(*parts: str) -> Path:
    """Create (if needed) and return a directory under the ignored
    ``experiments/runtime/`` tree for a bench's scratch output."""
    p = RUNTIME_OUT.joinpath(*parts)
    p.mkdir(parents=True, exist_ok=True)
    return p


def run_cpu_child(args, *, timeout: float) -> str:
    """Run ``python <args>`` as a fake-CPU-device child and return the
    last line of its stdout; a failed child raises.

    The child gets ``JAX_PLATFORMS=cpu`` (it stages its own host device
    count through XLA_FLAGS), so it never reaches for an accelerator
    that this process may already hold.
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, *map(str, args)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"child {list(args)} exited {proc.returncode}:"
                           f"\n{proc.stderr[-2000:]}")
    lines = proc.stdout.strip().splitlines()
    return lines[-1] if lines else ""


def write_json(name: str, obj):
    """Write one benchmark artifact under experiments/.

    Every artifact is stamped with a ``schema`` id (derived from the
    file name: ``repro.benchmarks/<stem>/v1``) and a ``generated_at``
    UTC timestamp, so downstream tooling (the observe report CLI, CI
    artifact diffing) can identify and order what it is reading.
    Payload keys win on collision — a bench that declares its own
    ``schema`` keeps it.
    """
    OUT.mkdir(exist_ok=True)
    p = OUT / name
    stamped = {"schema": f"repro.benchmarks/{p.stem}/v1",
               "generated_at": datetime.now(timezone.utc).isoformat()}
    if isinstance(obj, dict):
        stamped.update(obj)
    else:
        stamped["data"] = obj
    p.write_text(json.dumps(stamped, indent=2, default=str))
    return p


def fmt_table(rows, headers):
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
              for i, h in enumerate(headers)]
    out = ["  ".join(str(h).ljust(w) for h, w in zip(headers, widths))]
    out.append("  ".join("-" * w for w in widths))
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(out)
