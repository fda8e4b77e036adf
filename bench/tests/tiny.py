"""A small copy of the benchmark's cells for CPU tests: the same runners,
traffic kinds, reference and limits, on a graph of a few thousand nodes
with narrow layers, registered from a temporary directory."""
from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CELLS = {"gcn-arxiv.train": "train", "sage-arxiv.train": "train",
         "gcn-arxiv.infer": "infer", "gcn-arxiv.serve": "serve_open"}


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def make_root(tmp: str, n: int = 1200, width: int = 32, k: int = 32,
              cells=tuple(CELLS)) -> str:
    """A root holding a copy of the benchmark's files, whose BENCHMARK.json
    holds ``cells`` at a small size; the limits are the real cells'."""
    bench = _read("BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"),
                    dirs_exist_ok=True)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(tmp, "src"))
    for c in bench["configs"]:
        cfg = _read(c["file"])
        cfg["graph"].update(n=n, f=width, classes=8)
        cfg["model"].update(hidden=width, k=k)
        cfg["batch"] = -(-n // 4)
        c["file"] = f"bench/configs/{c['name']}.json"
        with open(os.path.join(tmp, c["file"]), "w") as f:
            json.dump(cfg, f)
    serve = _read("bench", "traffic", "serve_open.json")
    serve.update(rate_rps=300, slots=128, checked=20)
    with open(os.path.join(tmp, "bench", "traffic", "serve_open.json"),
              "w") as f:
        json.dump(serve, f)
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] in cells]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
