"""A throwaway checkout for CPU runs of the harness: the benchmark's files
copied, the program linked, and a BENCHMARK.json whose cells are the
committed configurations cut to a 32 x 24 grid."""

from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

from conftest import BENCH_DIR, ROOT

SMALL = {"nx": 32, "ny": 24}


def small_config(base: str, **override) -> dict:
    cfg = json.loads((BENCH_DIR / "configs" / f"{base}.json").read_text())
    cfg["problem"] = dict(cfg["problem"], **SMALL)
    cfg.update(override)
    return cfg


def make_checkout(tmp: Path, configs: dict[str, dict]) -> Path:
    """``tmp`` as a checkout whose BENCHMARK.json is the committed one with
    each configuration file replaced by ``configs[name]``."""
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(ROOT / "src", tmp / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        if entry["name"] in configs:
            path = tmp / entry["file"]
            path.write_text(json.dumps(configs[entry["name"]]))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, workload: str, capsys, seed: int = 2**31 + 3,
        seconds: float = 0.5) -> tuple[int, dict | None]:
    """One CPU run of ``workload`` in ``root``: (exit code, the result the
    last stdout line carries, or None)."""
    import run as entry

    rc = entry.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", "0"],
                    root=root, bench_dir=root / "benchmarks" / "chip",
                    require_chip=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]) if rc == 0 else None
